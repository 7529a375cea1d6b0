"""The chunked fused loss under a mesh: values, gradients and the table's collectives.

Since PR 39 the weightless call (`ops/loss.fused_linear_cross_entropy`: the summed rule)
forms its gradients in the differentiated forward, a token block against a device's whole
share of the vocabulary. Under tp ("act_vocab" -> tp) the block's logits stay vocab-sharded
and the hidden states' gradient is reduced over tp once a block; under ZeRO-3 (the table
arrives fsdp-sharded) the table must be gathered once, outside the block walk: the compiled
program is read for both. Counts on this mesh before PR 25 (same shapes, same helper): 3
all-gathers of the table under dp 2 x fsdp 2 x tp 2 (one `[V/tp, H]`, two transposed) and 1
under fsdp 8 (where its unconstrained backward gathered every hidden chunk inside the loop
instead); since PR 25 1 and 1, none inside a loop. The per-token rule's plan
(`plan_loss_backward`: it still tiles the vocabulary inside each tp shard) is held by the
last test.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dolomite_engine_tpu.ops.loss import (
    _chunked_ce_terms,
    _chunked_operands,
    fused_linear_cross_entropy,
    plan_loss_backward,
    plan_loss_blocks,
)
from dolomite_engine_tpu.parallel.sharding import get_logical_axis_rules
from dolomite_engine_tpu.utils.program_signature import hlo_collectives

B, S, H, V, CHUNK = 8, 64, 32, 512, 8
PARENT_TABLE_ALL_GATHERS = {"tp": 3, "fsdp": 1}


def _inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(ks[0], (B, S, H), jnp.float32)
    table = jax.random.normal(ks[1], (V, H), jnp.float32) * 0.05
    labels = jax.random.randint(ks[2], (B, S), 0, V).at[0, :5].set(-100)
    return hidden, table, labels


def _loss(labels):
    def loss(h, t):
        return fused_linear_cross_entropy(
            h, t, labels, chunk_size=CHUNK, compute_dtype=jnp.float32, z_loss_coef=1e-3
        )

    return loss


def _run(mesh, rules, table_spec):
    hidden, table, labels = _inputs()
    loss = _loss(labels)
    reference = jax.value_and_grad(loss, argnums=(0, 1))(hidden, table)

    plans = []

    def sharded_loss(h, t):
        with nn.logical_axis_rules(rules):
            plans.append(plan_loss_blocks(B, S // CHUNK, CHUNK, V, H, 4))
            return loss(h, t)

    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, None))
    table_sharding = NamedSharding(mesh, table_spec)
    with mesh:
        step = jax.jit(
            jax.value_and_grad(sharded_loss, argnums=(0, 1)),
            out_shardings=(None, (batch_sharding, table_sharding)),
        )
        args = jax.device_put(hidden, batch_sharding), jax.device_put(table, table_sharding)
        compiled = step.lower(*args).compile()
        value, grads = step(*args)
    np.testing.assert_allclose(float(value), float(reference[0]), rtol=0, atol=2e-6)
    for got, want in zip(grads, reference[1]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1.2e-7)
    return *plans[0], hlo_collectives(compiled.as_text()), compiled.as_text()


def _table_all_gathers(collectives, vocab_shards: int) -> list:
    local = {(V // vocab_shards, H), (H, V // vocab_shards), (V, H), (H, V)}
    return [c for c in collectives if c[0] == "all-gather" and c[1] in local]


def test_fused_loss_with_the_table_over_tp_matches_one_device(mesh_2x2x2):
    rules = get_logical_axis_rules(stage=3, tensor_parallel_word_embeddings=True)
    plan, record, collectives, _ = _run(mesh_2x2x2, rules, P("tp", "fsdp"))
    # one block of a device's tokens (B 8 over dp x fsdp) against its tp shard's 256 rows
    assert (record["vocab_shards"], plan.vocab_axes, plan.batch_axes) == (2, "tp", ("dp", "fsdp", "ep"))
    assert plan.token_blocks == 1 and plan.constrain
    gathers = _table_all_gathers(collectives, 2)
    # the table's embed axis is gathered over fsdp once, by the forward; never in a loop
    assert len(gathers) == 1 <= PARENT_TABLE_ALL_GATHERS["tp"], collectives
    assert not [c for c in collectives if c[0] == "all-gather" and c[2]], collectives


def test_fused_loss_with_the_table_over_fsdp_matches_one_device(mesh_fsdp8):
    plan, record, collectives, text = _run(mesh_fsdp8, get_logical_axis_rules(stage=3), P("fsdp", None))
    assert (record["vocab_shards"], plan.vocab_axes, plan.batch_axes) == (1, None, ("dp", "fsdp", "ep"))
    gathers = _table_all_gathers(collectives, 1)
    # ZeRO-3: one gather of the table for the whole rule
    assert len(gathers) == 1 <= PARENT_TABLE_ALL_GATHERS["fsdp"], collectives
    assert not [c for c in collectives if c[0] == "all-gather" and c[2]], collectives
    # the table's gradient leaves ONE product and is reduced over the batch shards once,
    # whole (with the loss's sums, in one all-reduce): no loop is left to reduce in (the
    # tiled rule reduced a tile at a time, the rule before it a [V, H] every chunk)
    reduced = re.findall(rf"= [^=]*f32\[{V},{H}\][^=]* (?:all-reduce|reduce-scatter)(?:-start)?\(", text)
    assert len(reduced) == 1 and " while(" not in text, collectives


@pytest.mark.parametrize(
    "planned_from, token_blocks",
    [((16, 16, 256, 49152), 2), ((32, 16, 256, 49152), 4)],
    ids=["two_blocks", "four_blocks"],
)
def test_block_walk_under_fsdp_x_tp_matches_one_device(mesh_2x2x2, planned_from, token_blocks):
    """More tokens a device than one block may keep the logits of, under dp 2 x fsdp 2 x tp 2
    with the table over tp: the plan of the shipped job's shapes (micro batch 4 and 8 a
    device, bf16 logits, half the vocabulary a device: no knob is turned) drives the rule at
    this file's size. Loss and gradients are one device's; the table is gathered once,
    OUTSIDE the walk, and nothing in the walk gathers anything."""
    rules = get_logical_axis_rules(stage=3, tensor_parallel_word_embeddings=True)
    hidden, table, labels = _inputs()
    reference = jax.value_and_grad(_loss(labels), argnums=(0, 1))(hidden, table)

    def walked(h, t):
        with nn.logical_axis_rules(rules):
            blocks, record = plan_loss_blocks(*planned_from, 2560, 2)
            assert (blocks.token_blocks, record["vocab_shards"], blocks.constrain) == (token_blocks, 2, True)
            hidden_c, labels_c, emb = _chunked_operands(h, t, labels, CHUNK, jnp.float32)
            objective, num_tokens = _chunked_ce_terms(hidden_c, labels_c, emb, None, True, jnp.float32, 1e-3, blocks)
        return objective / num_tokens

    batch_sharding = NamedSharding(mesh_2x2x2, P(("dp", "fsdp"), None, None))
    table_sharding = NamedSharding(mesh_2x2x2, P("tp", "fsdp"))
    with mesh_2x2x2:
        step = jax.jit(
            jax.value_and_grad(walked, argnums=(0, 1)),
            out_shardings=(None, (batch_sharding, table_sharding)),
        )
        args = jax.device_put(hidden, batch_sharding), jax.device_put(table, table_sharding)
        collectives = hlo_collectives(step.lower(*args).compile().as_text())
        value, grads = step(*args)
    np.testing.assert_allclose(float(value), float(reference[0]), rtol=0, atol=2e-6)
    for got, want in zip(grads, reference[1]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1.2e-7)
    assert [c for c in collectives if c[2]], collectives  # there IS a walk, and it reduces
    assert len(_table_all_gathers(collectives, 2)) == 1, collectives
    assert not [c for c in collectives if c[0] == "all-gather" and c[2]], collectives


@pytest.mark.parametrize("tensor_parallel", [False, True])
def test_plan_counts_tokens_and_rows_a_device(mesh_2x2x2, tensor_parallel):
    """The shipped job's shapes (micro batch 4 a device, 4096 tokens, chunk 256) on this
    mesh: with the table over tp a rule sees half the rows — the per-token rule tiles inside
    a shard, the summed rule keeps twice the tokens a block."""
    rules = get_logical_axis_rules(stage=3, tensor_parallel_word_embeddings=tensor_parallel)
    with mesh_2x2x2, nn.logical_axis_rules(rules):
        plan, record = plan_loss_backward(16, 16, 256, 49152, 2560)
        blocks, kept = plan_loss_blocks(16, 16, 256, 49152, 2560, 2)
    assert record["tokens_per_device"] == kept["tokens_per_device"] == 16384 and plan.constrain and blocks.constrain
    if tensor_parallel:
        assert (plan.vocab_shards, plan.token_blocks, plan.vocab_tiles, plan.tile_rows) == (2, 4, 4, 6144)
        assert (kept["vocab_shards"], blocks.token_blocks) == (2, 2)
    else:
        assert (plan.vocab_shards, plan.token_blocks, plan.vocab_tiles, plan.tile_rows) == (1, 2, 8, 6144)
        assert (kept["vocab_shards"], blocks.token_blocks) == (1, 4)
    assert record["table_carry_bytes"] == kept["table_carry_bytes"] == 4 * 2560 * 49152 // plan.vocab_shards
    assert kept["kept_logits_bytes"] == 384 * 2**20 and (record["logits_products"], kept["logits_products"]) == (2, 1)
