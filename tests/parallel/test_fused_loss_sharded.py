"""The chunked fused loss under a mesh: values, gradients and the table's collectives.

The backward rule of `ops/loss.fused_linear_cross_entropy` tiles the vocabulary. Under tp
("act_vocab" -> tp) a tile must be cut inside each shard's rows, and under ZeRO-3 (the table
arrives fsdp-sharded) the table must be gathered once, not once a tile: the compiled
program is read for both. Counts on this mesh before the rule was rewritten (the parent of
PR 25, same shapes, same helper): 3 all-gathers of the table under dp 2 x fsdp 2 x tp 2
(one `[V/tp, H]`, two transposed) and 1 under fsdp 8 (where its unconstrained backward
gathered every hidden chunk inside the loop instead); now 1 and 1, none inside a loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy, plan_loss_backward
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
            plans.append(plan_loss_backward(B, S // CHUNK, CHUNK, V, H)[0])
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
    return plans[0], hlo_collectives(compiled.as_text())


def _table_all_gathers(collectives, vocab_shards: int) -> list:
    local = {(V // vocab_shards, H), (H, V // vocab_shards), (V, H), (H, V)}
    return [c for c in collectives if c[0] == "all-gather" and c[1] in local]


def test_fused_loss_with_the_table_over_tp_matches_one_device(mesh_2x2x2):
    rules = get_logical_axis_rules(stage=3, tensor_parallel_word_embeddings=True)
    plan, collectives = _run(mesh_2x2x2, rules, P("tp", "fsdp"))
    # tiles are cut inside each tp shard's 256 rows, tokens counted a device (B 8 over dp x fsdp)
    assert (plan.vocab_shards, plan.vocab_axes, plan.batch_axes) == (2, "tp", ("dp", "fsdp", "ep"))
    assert plan.vocab_tiles * plan.tile_rows == V // 2 and plan.token_blocks * plan.vocab_tiles >= S // CHUNK
    gathers = _table_all_gathers(collectives, 2)
    # the table's embed axis is gathered over fsdp once, by the forward; never per tile
    assert len(gathers) == 1 <= PARENT_TABLE_ALL_GATHERS["tp"], collectives
    assert not [c for c in collectives if c[0] == "all-gather" and c[2]], collectives


def test_fused_loss_with_the_table_over_fsdp_matches_one_device(mesh_fsdp8):
    plan, collectives = _run(mesh_fsdp8, get_logical_axis_rules(stage=3), P("fsdp", None))
    assert (plan.vocab_shards, plan.vocab_axes, plan.batch_axes) == (1, None, ("dp", "fsdp", "ep"))
    gathers = _table_all_gathers(collectives, 1)
    # ZeRO-3: one gather of the table for both scans and the rule
    assert len(gathers) == 1 <= PARENT_TABLE_ALL_GATHERS["fsdp"], collectives
    assert not [c for c in collectives if c[0] == "all-gather" and c[2]], collectives
    # the table's gradient is reduced over the batch shards a tile at a time: the whole
    # table once a step, where the unchunked-backward scan reduced a [V, H] every chunk
    reduced = [c for c in collectives if c[0] in ("all-reduce", "reduce-scatter") and c[2] and c[1][-1] == H]
    assert reduced and all(np.prod(dims) <= plan.tile_rows * H for _, dims, _ in reduced), collectives


@pytest.mark.parametrize("tensor_parallel", [False, True])
def test_plan_counts_tokens_and_rows_a_device(mesh_2x2x2, tensor_parallel):
    """The shipped job's shapes (micro batch 4 a device, 4096 tokens, chunk 256) on this
    mesh: with the table over tp the rule sees half the rows and tiles inside a shard."""
    rules = get_logical_axis_rules(stage=3, tensor_parallel_word_embeddings=tensor_parallel)
    with mesh_2x2x2, nn.logical_axis_rules(rules):
        plan, record = plan_loss_backward(16, 16, 256, 49152, 2560)
    assert record["tokens_per_device"] == 16384 and plan.constrain
    if tensor_parallel:
        assert (plan.vocab_shards, plan.token_blocks, plan.vocab_tiles, plan.tile_rows) == (2, 4, 4, 6144)
    else:
        assert (plan.vocab_shards, plan.token_blocks, plan.vocab_tiles, plan.tile_rows) == (1, 2, 8, 6144)
    assert record["table_carry_bytes"] == 4 * 2560 * 49152 // plan.vocab_shards
