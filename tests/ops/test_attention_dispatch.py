"""Attention dispatch guards (parity: reference `attention_implementation_test.py` /
`attention_support_test.py` / `typecheck_test.py` — unsupported combinations must fail or
fall back LOUDLY, never silently compute the wrong thing)."""

import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import AttentionImplementation
from dolomite_engine_tpu.models import config_from_dict, get_model_class
from dolomite_engine_tpu.ops.attention import attention

import jax


def _qkv(B=1, S=4, H=2, D=4, S_kv=None):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S_kv or S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S_kv or S, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("impl", list(AttentionImplementation))
def test_every_implementation_builds_and_runs_dense(impl):
    """Every declared implementation constructs a model and produces finite logits
    (flash/ring fall back to sdpa on CPU / non-sp meshes — by design, with a warning)."""
    config = config_from_dict(
        dict(
            model_type="gpt_dolomite",
            vocab_size=64,
            n_positions=16,
            n_embd=32,
            n_layer=1,
            n_head=2,
            attention_head_type="mha",
            position_embedding_type="rope",
        )
    )
    model = get_model_class("gpt_dolomite")(config=config, attention_implementation=impl)
    ids = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    out = model.apply(variables, ids)
    assert bool(jnp.isfinite(out.logits).all())


def test_segment_ids_with_kv_cache_raises():
    """Packed segment attention over a longer KV cache is unsupported — must raise, not
    silently mis-mask (ops/attention.py guard)."""
    q, k, v = _qkv(S=2, S_kv=8)
    seg = jnp.ones((1, 2), jnp.int32)
    with pytest.raises(NotImplementedError, match="KV cache"):
        attention(q, k, v, implementation=AttentionImplementation.sdpa, segment_ids=seg)


def test_eager_and_sdpa_agree():
    q, k, v = _qkv(S=8)
    mask = jnp.asarray([[0, 0, 1, 1, 1, 1, 1, 1]], jnp.int32)
    a = attention(q, k, v, implementation=AttentionImplementation.eager, attention_mask=mask)
    b = attention(q, k, v, implementation=AttentionImplementation.sdpa, attention_mask=mask)
    real = np.asarray(mask, bool)
    np.testing.assert_allclose(np.asarray(a)[real], np.asarray(b)[real], atol=1e-5, rtol=1e-5)


def test_ring_without_sp_mesh_falls_back(caplog):
    """implementation=ring outside an sp>1 mesh must compute sdpa results (not crash)."""
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    MeshManager.destroy()
    q, k, v = _qkv(S=8)
    out_ring = attention(q, k, v, implementation=AttentionImplementation.ring)
    out_sdpa = attention(q, k, v, implementation=AttentionImplementation.sdpa)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_sdpa), atol=1e-6)


@pytest.mark.parametrize("sharded", [False, True], ids=["one_device", "mesh_2x2x2"])
def test_splash_attention_matches_sdpa_interpret(sharded, request):
    """Splash kernel (interpret mode) == sdpa for causal GQA with packed segment ids, fwd and
    grad (no KV-head repeat). Under a mesh the kernel must run per shard — batch over the
    data axes, whole GQA groups over tp — because GSPMD cannot partition a Mosaic kernel."""
    import contextlib

    from flax import linen as nn

    from dolomite_engine_tpu.ops.attention import _tpu_splash_attention, sdpa_attention, make_attention_mask
    from dolomite_engine_tpu.parallel.sharding import get_logical_axis_rules

    scope = contextlib.ExitStack()
    if sharded:
        scope.enter_context(request.getfixturevalue("mesh_2x2x2"))
        scope.enter_context(nn.logical_axis_rules(get_logical_axis_rules(stage=3)))

    rng = np.random.RandomState(0)
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 128
    q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    seg = jnp.asarray(
        np.r_[np.full(96, 1), np.full(96, 2), np.full(64, 3)][None].repeat(B, 0), jnp.int32
    )
    scale = D**-0.5

    def splash(q, k, v):
        return _tpu_splash_attention(q, k, v, seg, scale, interpret=True)

    def ref(q, k, v):
        from dolomite_engine_tpu.ops.attention import _repeat_kv

        mask = make_attention_mask(B, S, S, causal=True, segment_ids_q=seg)
        return sdpa_attention(_repeat_kv(q, Hq), _repeat_kv(k, Hq), _repeat_kv(v, Hq), mask, None, scale)

    expected = ref(q, k, v)
    g_r = jax.grad(lambda a, b, c: ref(a, b, c).sum(), argnums=(0, 1, 2))(q, k, v)
    with scope:
        assert ("shard_map" in str(jax.make_jaxpr(splash)(q, k, v))) == sharded
        out = jax.jit(splash)(q, k, v)
        g_s = jax.jit(jax.grad(lambda a, b, c: splash(a, b, c).sum(), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)
    for s_, r_ in zip(g_s, g_r):
        np.testing.assert_allclose(np.asarray(s_), np.asarray(r_), atol=5e-5, rtol=5e-5)
