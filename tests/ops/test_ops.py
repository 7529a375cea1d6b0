"""Unit tests for ops: activations, norms, rope/yarn, alibi, packing, loss, schedules.

Parity: reference `tests/hf_models/single_gpu/normalization_test.py`, `activations_test.py`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import LRDecaySchedule
from dolomite_engine_tpu.ops.activations import get_activation_function, is_glu
from dolomite_engine_tpu.ops.alibi import get_alibi_slopes
from dolomite_engine_tpu.ops.loss import causal_lm_loss, cross_entropy_loss
from dolomite_engine_tpu.ops.normalization import layernorm, rmsnorm
from dolomite_engine_tpu.ops.packing import (
    cu_seqlens_to_segment_ids,
    pack_sequences,
    segment_ids_from_eos,
    segment_ids_to_cu_seqlens,
)
from dolomite_engine_tpu.ops.rope import RoPEParams, apply_rotary_pos_emb, get_cos_sin
from dolomite_engine_tpu.optimization.scheduler import get_scheduler_factor

from ..test_commons import assert_allclose


@pytest.mark.parametrize(
    "name",
    ["gelu", "gelu_pytorch_tanh", "relu", "silu", "swish", "mish", "tanh", "relu2", "laplace"],
)
def test_base_activations_match_torch(name):
    import torch
    from transformers.activations import ACT2FN

    torch_map = {
        "gelu": torch.nn.GELU(),
        "gelu_pytorch_tanh": torch.nn.GELU(approximate="tanh"),
        "relu": torch.nn.ReLU(),
        "silu": torch.nn.SiLU(),
        "swish": torch.nn.SiLU(),
        "mish": torch.nn.Mish(),
        "tanh": torch.nn.Tanh(),
        "relu2": ACT2FN["relu2"],
        "laplace": ACT2FN["laplace"],
    }
    x = np.linspace(-4, 4, 101).astype(np.float32)
    ours = np.asarray(get_activation_function(name)(jnp.asarray(x)))
    theirs = torch_map[name](torch.from_numpy(x)).numpy()
    assert_allclose(ours, theirs, atol=1e-5, rtol=1e-5)


def test_glu_chunk_order():
    # GLU: first chunk is up, second is gated (reference glu.py forward: x[0] * act(x[1]))
    x = jnp.asarray(np.concatenate([np.full(4, 3.0), np.full(4, -100.0)]).astype(np.float32))
    out = get_activation_function("swiglu")(x)
    # silu(-100) ~ 0 -> output ~ 0 (up=3 * act(gate=-100))
    assert float(jnp.max(jnp.abs(out))) < 1e-4
    assert is_glu("swiglu") and is_glu("glu") and not is_glu("gelu")


def test_norms_match_torch():
    import torch

    x = np.random.RandomState(0).randn(3, 17).astype(np.float32)
    w = np.random.RandomState(1).rand(17).astype(np.float32)
    b = np.random.RandomState(2).randn(17).astype(np.float32)

    ln_ref = torch.nn.functional.layer_norm(
        torch.from_numpy(x), (17,), torch.from_numpy(w), torch.from_numpy(b), 1e-5
    ).numpy()
    assert_allclose(layernorm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), 1e-5), ln_ref, atol=1e-5)

    rms_ref = torch.from_numpy(x) * torch.rsqrt(
        torch.from_numpy(x).pow(2).mean(-1, keepdim=True) + 1e-6
    )
    rms_ref = (rms_ref * torch.from_numpy(w)).numpy()
    assert_allclose(rmsnorm(jnp.asarray(x), jnp.asarray(w), 1e-6), rms_ref, atol=1e-5)


def test_rope_rotation_preserves_norm_and_relative_positions():
    rope = RoPEParams.from_config(head_dim=16, base=10000)
    pos = jnp.arange(8)[None]
    cos, sin = get_cos_sin(rope, pos)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 8, 2, 16).astype(np.float32))
    rx = apply_rotary_pos_emb(x, cos, sin)
    assert_allclose(
        jnp.linalg.norm(rx, axis=-1), jnp.linalg.norm(x, axis=-1), atol=1e-4, rtol=1e-4
    )
    # relative property: <R(p)q, R(p+k)v> depends only on k
    q = x[:, :1]
    dots = []
    for p in range(4):
        cq, sq = get_cos_sin(rope, jnp.asarray([[p]]))
        ck, sk = get_cos_sin(rope, jnp.asarray([[p + 3]]))
        rq = apply_rotary_pos_emb(q, cq, sq)
        rk = apply_rotary_pos_emb(q, ck, sk)
        dots.append(float(jnp.sum(rq * rk)))
    assert max(dots) - min(dots) < 1e-3


def test_yarn_mscale_and_inv_freq():
    plain = RoPEParams.from_config(head_dim=16, base=10000)
    yarn = RoPEParams.from_config(
        head_dim=16,
        base=10000,
        rope_scaling={"type": "yarn", "factor": 4.0, "original_max_position_embeddings": 128},
        max_position_embeddings=512,
    )
    assert yarn.mscale == pytest.approx(0.1 * math.log(4.0) + 1.0)
    # interpolated freqs are slower (smaller) than plain, never faster
    assert np.all(yarn.inv_freq <= plain.inv_freq + 1e-9)


def test_alibi_slopes_non_pow2():
    s8 = get_alibi_slopes(8)
    assert s8.shape == (8,)
    assert_allclose(s8[0], 2 ** (-8 / 8.0 * 1), atol=1e-6)
    s6 = get_alibi_slopes(6)  # non-power-of-2 head count extension
    assert s6.shape == (6,) and np.all(s6 > 0)


def test_packing_roundtrip():
    packed = pack_sequences([[5, 6, 7], [8, 9]], max_length=8, pad_token_id=0)
    assert packed["segment_ids"].tolist() == [[1, 1, 1, 2, 2, 0, 0, 0]]
    assert packed["position_ids"].tolist() == [[0, 1, 2, 0, 1, 0, 0, 0]]
    cu = segment_ids_to_cu_seqlens(packed["segment_ids"])
    assert cu.tolist() == [0, 3, 5]
    seg = cu_seqlens_to_segment_ids(cu, 8)
    assert seg.tolist() == [1, 1, 1, 2, 2, 0, 0, 0]


def test_segment_ids_from_eos():
    tokens = np.asarray([[3, 4, 1, 5, 6, 7, 1, 8]])  # eos = 1
    seg, pos = segment_ids_from_eos(tokens, eos_token_id=1)
    assert seg.tolist() == [[1, 1, 1, 2, 2, 2, 2, 3]]
    assert pos.tolist() == [[0, 1, 2, 0, 1, 2, 3, 0]]


def test_cross_entropy_ignore_index():
    logits = jnp.asarray(np.random.RandomState(0).randn(2, 4, 10).astype(np.float32))
    labels = jnp.asarray([[1, 2, -100, 3], [-100, -100, 5, 6]])
    loss_sum, n = cross_entropy_loss(logits, labels)
    assert int(n) == 5
    full = causal_lm_loss(logits, jnp.zeros((2, 4), jnp.int32), labels=labels)
    assert_allclose(full, loss_sum / n)


@pytest.mark.parametrize(
    "style", [LRDecaySchedule.constant, LRDecaySchedule.cosine, LRDecaySchedule.linear, LRDecaySchedule.exponential]
)
def test_scheduler_boundaries(style):
    f = get_scheduler_factor(10, 5, None, 100, style, 0.1)
    assert float(f(0)) == pytest.approx(0.0)
    assert float(f(10)) == pytest.approx(1.0)
    assert float(f(12)) == pytest.approx(1.0)
    if style != LRDecaySchedule.constant:
        assert float(f(100)) == pytest.approx(0.1, abs=1e-5)
        assert float(f(50)) < 1.0
    else:
        assert float(f(100)) == pytest.approx(1.0)


def test_power_scheduler():
    f = get_scheduler_factor(
        10, 0, None, 100, LRDecaySchedule.power, 0.1,
        extra_lr_scheduler_args={"a": 1e-2, "b": -0.51, "c": 512}, base_lr=1e-3,
    )
    assert float(f(5)) <= float(f(10)) <= 1.0
    assert float(f(50)) <= 1.0


@pytest.mark.parametrize(
    "B, S, V, chunk",
    [(2, 8, 32, 4), (2, 8, 33, 4), (4, 16, 8, 2), (1, 10, 32, 5)],
    ids=["tiles", "padded_vocab", "token_blocks", "one_row"],
)
def test_fused_linear_cross_entropy_matches_plain(B, S, V, chunk):
    """Fused chunked LM-head loss == materialized logits path, values AND grads."""
    import numpy as np

    from dolomite_engine_tpu.ops.loss import IGNORE_INDEX, fused_linear_cross_entropy

    rng = np.random.RandomState(0)
    H = 16
    hidden = jnp.asarray(rng.randn(B, S, H), jnp.float32)
    emb = jnp.asarray(rng.randn(V, H) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, size=(B, S)), jnp.int32)
    labels = labels.at[0, -1].set(IGNORE_INDEX).at[-1, 0].set(IGNORE_INDEX)

    def plain(h, e):
        logits = jnp.dot(h, e.T)
        return causal_lm_loss(logits, jnp.zeros((B, S), jnp.int32), labels=labels)

    def fused(h, e, chunk_size=chunk):
        return fused_linear_cross_entropy(
            h, e, labels, chunk_size=chunk_size, compute_dtype=jnp.float32
        )

    lp, (ghp, gep) = jax.value_and_grad(plain, argnums=(0, 1))(hidden, emb)
    lf, (ghf, gef) = jax.value_and_grad(fused, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(lp, lf, rtol=1e-6)
    np.testing.assert_allclose(ghp, ghf, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gep, gef, rtol=1e-5, atol=1e-6)

    # non-divisible seq pads up to a chunk multiple with IGNORE labels, still exact
    lf2, (ghf2, gef2) = jax.value_and_grad(
        lambda h, e: fused(h, e, chunk_size=chunk + 1), argnums=(0, 1)
    )(hidden, emb)
    np.testing.assert_allclose(lp, lf2, rtol=1e-6)
    np.testing.assert_allclose(ghp, ghf2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gep, gef2, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "batch, n_chunks, chunk, vocab, expected",
    [
        (1, 16, 256, 49152, (1, 16, 3072)),  # the benchmark's cells: one packed row of 4096
        (4, 16, 256, 49152, (2, 8, 6144)),  # the shipped job's micro batch on one device
        (1, 128, 256, 49152, (8, 16, 3072)),  # one row of 32768 tokens: long context
        (1, 16, 256, 50257, (1, 16, 3200)),  # a prime vocabulary: padded, lane-aligned tiles
        (2, 4, 7, 211, (1, 4, 53)),
        (8, 1, 64, 50257, (1, 1, 50257)),  # one chunk: one block, one tile, no loop carries
    ],
)
def test_plan_loss_backward_follows_the_shapes(batch, n_chunks, chunk, vocab, expected):
    """The backward rule's tiling is chosen from the shapes alone: live logits stay at the
    forward's budget, and the float32 bytes its loops carry never exceed those of the
    corner it used to sit in (a token block a chunk, one tile: the whole table gradient
    through HBM once a chunk)."""
    from dolomite_engine_tpu.ops.loss import plan_loss_backward

    hidden_size = 64
    tiling, record = plan_loss_backward(batch, n_chunks, chunk, vocab, hidden_size)
    assert (tiling.token_blocks, tiling.vocab_tiles, tiling.tile_rows) == expected
    assert not tiling.constrain and tiling.vocab_shards == 1  # no mesh here
    assert n_chunks % tiling.token_blocks == 0
    assert tiling.token_blocks * tiling.vocab_tiles >= n_chunks  # the live-logits budget
    assert vocab <= tiling.vocab_tiles * tiling.tile_rows < vocab + 128 * tiling.vocab_tiles
    old_corner = 4 * hidden_size * (2 * n_chunks * vocab + batch * n_chunks * chunk)
    assert record["accumulator_bytes_moved"] <= (old_corner if n_chunks > 1 else old_corner + 4 * hidden_size * vocab)
    assert record["table_carry_bytes"] == (0 if tiling.token_blocks == 1 else 4 * hidden_size * tiling.vocab_tiles * tiling.tile_rows)


# ---- the summed rule (PR 39): the head's logits are computed once, the differentiated forward
# forms both gradients over a token block's kept logits, the backward rule only scales them

_SUMMED_NUMERICS = {
    "fp32": dict(),
    "fp32_z_loss": dict(z_loss_coef=1e-3),
    "fp32_logit_scale_z_loss": dict(logit_scale=0.125, z_loss_coef=1e-3),
    "bf16": dict(compute_dtype=jnp.bfloat16),
    "bf16_z_loss_logit_scale": dict(compute_dtype=jnp.bfloat16, z_loss_coef=1e-3, logit_scale=0.5),
    "bf16_no_upcast": dict(compute_dtype=jnp.bfloat16, upcast=False),
    "bf16_no_upcast_z_loss": dict(compute_dtype=jnp.bfloat16, upcast=False, z_loss_coef=1e-3),
}


def _summed_operands(B=3, S=22, H=16, V=53, seed=0):
    """A sequence no chunk of 8 divides, a vocabulary no tile divides (a prime), rows without
    a label at both ends of a row and across a chunk's edge."""
    rng = np.random.RandomState(seed)
    hidden = jnp.asarray(rng.randn(B, S, H), jnp.float32)
    table = jnp.asarray(rng.randn(V, H) * 0.3, jnp.float32)
    labels = rng.randint(0, V, size=(B, S))
    labels[0, -1] = labels[-1, 0] = labels[1, 6:10] = -100
    return hidden, table, jnp.asarray(labels, jnp.int32)


def _unchunked_loss(hidden, table, labels, compute_dtype=jnp.float32, logit_scale=None, upcast=True, z_loss_coef=0.0):
    logits = jnp.dot(hidden.astype(compute_dtype), table.astype(compute_dtype).T)
    if logit_scale is not None:
        logits = logits * logit_scale
    return causal_lm_loss(logits, jnp.zeros(labels.shape, jnp.int32), labels=labels, z_loss_coef=z_loss_coef, upcast=upcast)


def _assert_follows(got, want, compute_dtype, upcast=True):
    """A loss and both gradients against the unchunked reference's: float32 to an ulp or two
    (another order of summation); bf16 within 2 bf16 ulp of the gradient's largest entry (the
    reference rounds its own gradients to bf16), the band the tiled rule had."""
    (loss, grads), (ref_loss, ref_grads) = got, want
    fp32 = jnp.dtype(compute_dtype) == jnp.float32
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=0, atol=2e-6 if upcast else 0.1)
    for g, r in zip(grads, ref_grads):
        atol = 2e-7 if fp32 else 2 * 2.0**-7 * float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0, atol=atol)


@pytest.mark.parametrize("numerics", list(_SUMMED_NUMERICS))
def test_summed_rule_follows_the_unchunked_loss(numerics):
    """Value and both gradients of `fused_linear_cross_entropy` without weights — the rule whose
    differentiated forward keeps a token block's logits and forms the gradients there — against
    `causal_lm_loss` on whole logits, in float32 and bf16, with and without the z-loss,
    `logit_scale` and `upcast`; and the undifferentiated call (the chunk scan) gives the
    differentiated forward's value."""
    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy

    options = _SUMMED_NUMERICS[numerics]
    hidden, table, labels = _summed_operands()
    fused = lambda h, t: fused_linear_cross_entropy(h, t, labels, chunk_size=8, **{"compute_dtype": jnp.float32, **options})  # noqa: E731
    got = jax.value_and_grad(fused, argnums=(0, 1))(hidden, table)
    want = jax.value_and_grad(lambda h, t: _unchunked_loss(h, t, labels, **options), argnums=(0, 1))(hidden, table)
    _assert_follows(got, want, options.get("compute_dtype", jnp.float32), options.get("upcast", True))
    # the scan sums a chunk at a time, the block all at once: an order of summation apart
    np.testing.assert_allclose(float(fused(hidden, table)), float(got[0]), rtol=0, atol=2e-6 if options.get("upcast", True) else 0.1)


def test_summed_rule_scales_its_kept_gradients_by_the_cotangent():
    """The backward rule only scales: a loss times 3 (gradient accumulation, an auxiliary
    head's weight) has 3 times the gradients, to float32's rounding of one product."""
    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy

    hidden, table, labels = _summed_operands()
    fused = lambda h, t: fused_linear_cross_entropy(h, t, labels, chunk_size=8, compute_dtype=jnp.float32, z_loss_coef=1e-3)  # noqa: E731
    once = jax.grad(fused, argnums=(0, 1))(hidden, table)
    thrice = jax.grad(lambda h, t: 3.0 * fused(h, t), argnums=(0, 1))(hidden, table)
    for a, b in zip(once, thrice):
        np.testing.assert_allclose(3.0 * np.asarray(a), np.asarray(b), rtol=2e-7, atol=0)


@pytest.mark.parametrize(
    "batch, n_chunks, chunk, vocab, itemsize, expected",
    [
        (1, 16, 256, 49152, 2, (1, 384 * 2**20)),  # the dense cells: one packed row of 4096, the budget itself
        (4, 16, 256, 49152, 2, (4, 384 * 2**20)),  # the shipped 3B job's micro batch: a row's worth a block
        (2, 32, 256, 16384, 2, (2, 256 * 2**20)),  # the 8k cells' heads at an eighth of the vocabulary
        (1, 16, 256, 49152, 4, (2, 384 * 2**20)),  # float32 logits: half the tokens a block
        (1, 128, 256, 49152, 2, (8, 384 * 2**20)),  # one row of 32768 tokens: long context
        (64, 16, 256, 49152, 2, (16, 1536 * 2**20)),  # one chunk passes the budget: a block is the scan's chunk
        (1, 16, 256, 50257, 2, (2, 2048 * 50257 * 2)),  # a prime vocabulary: nothing is padded, nothing tiled
        (2, 3, 7, 211, 4, (1, 2 * 3 * 7 * 211 * 4)),
    ],
)
def test_plan_loss_blocks_follows_the_shapes(batch, n_chunks, chunk, vocab, itemsize, expected):
    """The summed rule's blocks are chosen from the shapes alone: the fewest that keep a
    block's logits under the budget in bytes, a divisor of the chunks; one block carries
    nothing, several carry the table's float32 gradient once a block."""
    from dolomite_engine_tpu.ops.loss import _KEPT_LOGITS_BYTES, plan_loss_blocks

    hidden_size = 64
    blocks, record = plan_loss_blocks(batch, n_chunks, chunk, vocab, hidden_size, itemsize)
    assert (blocks.token_blocks, record["kept_logits_bytes"]) == expected
    assert not blocks.constrain and record["vocab_shards"] == 1 and n_chunks % blocks.token_blocks == 0  # no mesh here
    assert record["kept_logits_bytes"] <= _KEPT_LOGITS_BYTES or blocks.token_blocks == n_chunks
    if blocks.token_blocks > 1:  # one block fewer would not have fit
        fewer = max(d for d in range(1, blocks.token_blocks) if n_chunks % d == 0)
        assert batch * n_chunks * chunk // fewer * vocab * itemsize > _KEPT_LOGITS_BYTES
    assert record["logits_products"] == 1 and "hidden_carry_bytes" not in record and "vocab_tiles" not in record
    assert record["table_carry_bytes"] == (0 if blocks.token_blocks == 1 else 4 * hidden_size * vocab)
    trips = 1 if blocks.token_blocks == 1 else 2 * blocks.token_blocks
    assert record["accumulator_bytes_moved"] == 4 * hidden_size * (trips * vocab + batch * n_chunks * chunk)
    assert blocks.logits_block(batch, n_chunks, chunk, vocab) == (n_chunks // blocks.token_blocks, batch, chunk, vocab)


@pytest.mark.parametrize("z_loss_coef", [0.0, 1e-3], ids=["no_z_loss", "z_loss"])
@pytest.mark.parametrize(
    "planned_from, token_blocks",
    [((2, 32, 256, 16384, 2), 2), ((4, 16, 256, 49152, 2), 4)],
    ids=["two_blocks", "four_blocks"],
)
def test_summed_rule_walks_the_blocks_its_plan_gives(planned_from, token_blocks, z_loss_coef):
    """More tokens than one block may keep the logits of: the plan of shapes over the budget
    (the 8k cells' head, the shipped job's micro batch — no knob is turned) drives the rule
    at a toy's size; the walk carries the table's float32 gradient and gives the unchunked
    loss and gradients."""
    from dolomite_engine_tpu.ops.loss import _chunked_ce_terms, _chunked_operands, plan_loss_blocks

    blocks = plan_loss_blocks(*planned_from[:4], 64, planned_from[4])[0]
    assert blocks.token_blocks == token_blocks
    hidden, table, labels = _summed_operands(S=30)  # 4 chunks of 8, the last padded

    def walked(h, t):
        hidden_c, labels_c, emb = _chunked_operands(h, t, labels, 8, jnp.float32)
        assert hidden_c.shape[0] == 4
        objective, num_tokens = _chunked_ce_terms(hidden_c, labels_c, emb, None, True, jnp.float32, z_loss_coef, blocks)
        return objective / num_tokens

    got = jax.value_and_grad(walked, argnums=(0, 1))(hidden, table)
    want = jax.value_and_grad(lambda h, t: _unchunked_loss(h, t, labels, z_loss_coef=z_loss_coef), argnums=(0, 1))(hidden, table)
    _assert_follows(got, want, jnp.float32)
    loops = [eqn for eqn in jax.make_jaxpr(jax.grad(walked, argnums=(0, 1)))(hidden, table).jaxpr.eqns if eqn.primitive.name == "scan"]
    assert [eqn.params["length"] for eqn in loops] == [token_blocks]


def _dot_generals(jaxpr) -> list:
    """The output shapes of every `dot_general` in `jaxpr`, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_dot_generals(inner))
    return found


@pytest.mark.parametrize("rule", ["summed", "per_token"])
def test_differentiated_head_computes_its_logits_once(rule):
    """The structure of loss and gradients as one jaxpr: the summed rule holds three products
    — ONE whose output is a token block's ``[chunks, B, chunk, V]`` logits, and the two
    gradients' — and no loop at one block; the per-token rule, whose cotangents arrive after
    its forward, still holds four (the parent's count for both): the scan's ``[B, chunk, V]``
    and the backward's ``[chunks a block, B, chunk, shards, tile rows]`` are logits twice."""
    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy

    B, S, H, V, chunk = 2, 32, 16, 211, 8
    hidden, table, labels = jnp.zeros((B, S, H)), jnp.zeros((V, H)), jnp.zeros((B, S), jnp.int32)
    weights = jnp.ones((B, S)) if rule == "per_token" else None
    loss = lambda h, t: fused_linear_cross_entropy(h, t, labels, chunk_size=chunk, compute_dtype=jnp.float32, weights=weights)  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(hidden, table).jaxpr
    products = _dot_generals(jaxpr)
    logits = [shape for shape in products if H not in shape]
    if rule == "summed":
        assert logits == [(S // chunk, B, chunk, V)] and len(products) == 3
        assert sorted(p for p in products if p not in logits) == sorted([(S // chunk, B, chunk, H), (V, H)])
        assert not [eqn for eqn in jaxpr.eqns if eqn.primitive.name in ("scan", "while")]
    else:
        assert len(logits) == 2 and len(products) == 4 and (B, chunk, V) in logits


def test_fused_lm_head_loss_model_parity():
    """GPTDolomite with fused_lm_head_loss=True gives the same loss as the logits path."""
    import numpy as np

    from dolomite_engine_tpu.models import get_model_class
    from dolomite_engine_tpu.models.config import CommonConfig

    base = dict(
        vocab_size=64,
        n_positions=32,
        n_embd=32,
        n_layer=2,
        n_head=4,
        attention_head_type="mha",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        tie_word_embeddings=True,
    )
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, size=(2, 32)), jnp.int32)

    losses = {}
    for fused in (False, True):
        config = CommonConfig(**base, fused_lm_head_loss=fused, loss_chunk_size=8)
        cls = get_model_class(config.model_type)
        model = cls(config=config, dtype=jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), ids, compute_loss=True)
        out = model.apply(variables, ids, compute_loss=True)
        losses[fused] = out.loss
        assert (out.logits is None) == fused

    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)


def test_padding_mask_to_segment_ids_conversion_numerics():
    """flash_attention_2 with a 2D left-pad mask converts it to segment ids (ops/attention.py);
    on CPU that rides the sdpa fallback with segment masking — REAL rows must match the plain
    key-side-mask sdpa path exactly (pad rows are never read and may differ)."""
    import numpy as np

    from dolomite_engine_tpu.enums import AttentionImplementation
    from dolomite_engine_tpu.ops.attention import attention

    rng = np.random.RandomState(0)
    B, S, H, D = 2, 8, 2, 4
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    # left padding: row 0 pads 3, row 1 pads 0
    mask = jnp.asarray([[0, 0, 0, 1, 1, 1, 1, 1], [1] * 8], jnp.int32)

    ref = attention(
        q, k, v, implementation=AttentionImplementation.sdpa, causal=True,
        attention_mask=mask,
    )
    got = attention(
        q, k, v, implementation=AttentionImplementation.flash_attention_2, causal=True,
        attention_mask=mask,
    )
    real = np.asarray(mask, bool)
    np.testing.assert_allclose(
        np.asarray(got)[real], np.asarray(ref)[real], rtol=1e-6, atol=1e-6
    )
