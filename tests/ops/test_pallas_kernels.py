"""Interpret-mode parity suite for the Pallas kernel tier (`ops/pallas/`).

Every kernel runs in interpret mode on CPU (`utils/packages.pallas_interpret_mode`), so
this suite pins the numerics in tier-1 exactly like the splash-attention pattern:

- ragged paged-attention decode vs the `paged_gather_kv` + `eager_attention` reference
  (trash-page rows, ragged frontiers, the speculative K+1 window, GQA);
- fused RMSNorm(+residual) vs `ops/normalization.rmsnorm` at fp32/bf16 tolerances,
  forward and backward;
- grouped-GEMM MoE dispatch vs `experts_eager`, forward and backward, incl. empty
  expert groups and the EP path's local-compute body;
- the central KernelConfig (precedence, env parsing, legacy alias, capability gating);
- the serving engine with ``paged_attention=pallas``: decode_compiles == 1 and
  token-for-token parity vs `generate_tokens` with paged KV + prefix cache + chunked
  prefill + speculation all active.

Model paths are unsharded (no mesh) except `test_kernels_run_per_shard_under_a_mesh`:
GSPMD cannot partition a Mosaic kernel, so under a multi-device mesh every promoted
family runs through `parallel.sharding.shard_kernel`, checked there against XLA.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import KernelBackend
from dolomite_engine_tpu.generation_utils import generate_tokens
from dolomite_engine_tpu.models.config import CommonConfig
from dolomite_engine_tpu.models.gpt_dolomite import GPTDolomiteForCausalLM
from dolomite_engine_tpu.ops.attention import (
    eager_attention,
    make_attention_mask,
    paged_gather_kv,
)
from dolomite_engine_tpu.ops.moe import combine_weights, experts_eager, route
from dolomite_engine_tpu.ops.normalization import rmsnorm
from dolomite_engine_tpu.ops.pallas import (
    KERNEL_FAMILIES,
    KernelConfig,
    active_kernel_backends,
    get_kernel_config,
    install_kernel_config,
    kernel_overrides,
    platform_default_backend,
    resolved_kernel_backend,
    use_pallas,
)
from dolomite_engine_tpu.serving import ServingEngine

PAGE = 16


@pytest.fixture(autouse=True)
def _clean_kernel_selection(monkeypatch):
    """Isolate kernel selection per test: earlier suite tests may have run an entry
    point's ``kernel_args.install()`` (process-wide by design — it beats env), and the
    ambient environment may carry the override vars; both would leak into the
    precedence assertions here."""
    from dolomite_engine_tpu.ops.pallas import config as kernel_config_module

    monkeypatch.delenv("DOLOMITE_KERNELS", raising=False)
    previous = kernel_config_module._INSTALLED
    install_kernel_config(None)
    yield
    install_kernel_config(previous)


# ------------------------------------------------------------------- kernel config


def test_default_config_resolves_all_xla_off_tpu():
    # the raw default is `auto` everywhere; on the CPU tier it must RESOLVE to the
    # all-XLA reference lowering with no flags (the promotion table only fires on TPU)
    config = get_kernel_config()
    for family in KERNEL_FAMILIES:
        assert getattr(config, family) is KernelBackend.auto
        assert resolved_kernel_backend(family) is KernelBackend.xla
        assert not use_pallas(family)
    assert active_kernel_backends() == {f: "xla" for f in KERNEL_FAMILIES}


def test_env_override_parsing(monkeypatch):
    monkeypatch.setenv("DOLOMITE_KERNELS", "paged_attention, rmsnorm=pallas, moe_dispatch=xla")
    config = get_kernel_config()
    assert config.paged_attention is KernelBackend.pallas  # bare name -> pallas
    assert config.rmsnorm is KernelBackend.pallas
    assert config.moe_dispatch is KernelBackend.xla
    assert config.splash_attention is KernelBackend.auto  # untouched families stay auto
    assert resolved_kernel_backend("splash_attention") is KernelBackend.xla  # ...cpu


def test_env_override_unknown_family_raises(monkeypatch):
    monkeypatch.setenv("DOLOMITE_KERNELS", "flash_mlp")
    with pytest.raises(ValueError, match="unknown kernel family"):
        get_kernel_config()


def test_installed_config_beats_env(monkeypatch):
    monkeypatch.setenv("DOLOMITE_KERNELS", "rmsnorm")
    install_kernel_config({"moe_dispatch": "pallas", "rmsnorm": "xla"})
    try:
        config = get_kernel_config()
        assert config.moe_dispatch is KernelBackend.pallas
        assert config.rmsnorm is KernelBackend.xla  # env ignored while installed
    finally:
        install_kernel_config(None)
    assert get_kernel_config().rmsnorm is KernelBackend.pallas  # env resolution is back


def test_install_rejects_unknown_family_and_backend():
    with pytest.raises(ValueError, match="unknown kernel family"):
        install_kernel_config({"flash_mlp": "pallas"})
    with pytest.raises(ValueError, match="unknown kernel backend"):
        install_kernel_config({"rmsnorm": "triton"})
    assert get_kernel_config() == KernelConfig()  # failed installs left nothing behind


def test_kernel_overrides_restores_previous_state():
    assert not use_pallas("rmsnorm")
    with kernel_overrides(rmsnorm="pallas", paged_attention=KernelBackend.pallas):
        assert use_pallas("rmsnorm") and use_pallas("paged_attention")
        assert not use_pallas("moe_dispatch")
    assert not use_pallas("rmsnorm")
    assert get_kernel_config() == KernelConfig()


def test_kernel_args_block_installs():
    from dolomite_engine_tpu.arguments import KernelArgs

    KernelArgs(rmsnorm="pallas").install()
    try:
        assert use_pallas("rmsnorm")
        assert not use_pallas("moe_dispatch")
    finally:
        install_kernel_config(None)


# --------------------------------------------------- platform promotion defaults


@pytest.fixture
def _fake_tpu_platform(monkeypatch):
    """Pretend the detected platform is a v5e pod slice (promotion tables only; no
    kernel actually lowers for TPU in these tests)."""
    from dolomite_engine_tpu.ops.pallas import config as kernel_config_module

    monkeypatch.setattr(kernel_config_module, "_PLATFORM_KEY", "tpu:v5e")
    yield kernel_config_module


def test_platform_defaults_promote_on_tpu(_fake_tpu_platform):
    # the families the TPU compiler accepts lower Pallas on a v5e with NO flags; the two
    # it refuses (paged/prefill attention, ROADMAP S5) and the pending-A/B families stay
    # on the XLA reference by the table — not by a fallback at the call site
    assert platform_default_backend("rmsnorm") is KernelBackend.pallas
    assert platform_default_backend("splash_attention") is KernelBackend.pallas
    assert platform_default_backend("fused_rope_qkv") is KernelBackend.pallas
    assert platform_default_backend("paged_attention") is KernelBackend.xla
    assert platform_default_backend("prefill_attention") is KernelBackend.xla
    assert platform_default_backend("moe_dispatch") is KernelBackend.xla
    assert platform_default_backend("fused_ce") is KernelBackend.xla
    assert resolved_kernel_backend("rmsnorm") is KernelBackend.pallas
    assert use_pallas("rmsnorm")


def test_platform_defaults_per_generation_row(monkeypatch):
    from dolomite_engine_tpu.ops.pallas import config as kernel_config_module

    # v2/v3 use the conservative row: elementwise fusions only
    monkeypatch.setattr(kernel_config_module, "_PLATFORM_KEY", "tpu:v3")
    assert platform_default_backend("rmsnorm") is KernelBackend.pallas
    assert platform_default_backend("splash_attention") is KernelBackend.xla
    # an unknown future generation falls back to the generic tpu row
    monkeypatch.setattr(kernel_config_module, "_PLATFORM_KEY", "tpu:v9x")
    assert platform_default_backend("splash_attention") is KernelBackend.pallas


def test_promotion_precedence_auto_env_yaml(_fake_tpu_platform, monkeypatch):
    from dolomite_engine_tpu.arguments import KernelArgs

    # base: auto resolves through the platform table
    assert resolved_kernel_backend("rmsnorm") is KernelBackend.pallas
    # env beats auto: an explicit demotion wins over the table
    monkeypatch.setenv("DOLOMITE_KERNELS", "rmsnorm=xla")
    assert resolved_kernel_backend("rmsnorm") is KernelBackend.xla
    # ...and the untouched families keep resolving through the table
    assert resolved_kernel_backend("splash_attention") is KernelBackend.pallas
    # YAML (installed KernelArgs) beats env
    KernelArgs(rmsnorm="pallas", splash_attention="xla").install()
    try:
        assert resolved_kernel_backend("rmsnorm") is KernelBackend.pallas
        assert resolved_kernel_backend("splash_attention") is KernelBackend.xla
        # a family the YAML leaves on auto still resolves through the table
        assert resolved_kernel_backend("fused_rope_qkv") is KernelBackend.pallas
    finally:
        install_kernel_config(None)


def test_env_auto_spelling(_fake_tpu_platform, monkeypatch):
    # the literal item `auto` resets every family to platform defaults; later items
    # re-override per family
    monkeypatch.setenv("DOLOMITE_KERNELS", "auto")
    assert resolved_kernel_backend("rmsnorm") is KernelBackend.pallas
    assert resolved_kernel_backend("moe_dispatch") is KernelBackend.xla
    monkeypatch.setenv("DOLOMITE_KERNELS", "auto,rmsnorm=xla,fused_ce=auto")
    config = get_kernel_config()
    assert config.rmsnorm is KernelBackend.xla
    assert config.fused_ce is KernelBackend.auto
    assert resolved_kernel_backend("rmsnorm") is KernelBackend.xla
    assert resolved_kernel_backend("fused_ce") is KernelBackend.xla  # pending-A/B family


def test_pallas_that_cannot_be_honoured_is_an_error(_fake_tpu_platform, monkeypatch):
    """A build whose Pallas import fails: an explicit ``pallas`` and a TPU ``auto``
    promotion both raise — nothing trains on XLA while the record says pallas — and a
    family that resolves to xla never consults the probe."""
    from dolomite_engine_tpu.utils import packages

    monkeypatch.setattr(
        packages, "pallas_import_error", lambda: ImportError("no pallas in this build")
    )
    with kernel_overrides(fused_ce="pallas"):
        with pytest.raises(RuntimeError, match="'fused_ce' resolves to pallas"):
            use_pallas("fused_ce")
    with pytest.raises(RuntimeError, match="'rmsnorm' resolves to pallas"):
        use_pallas("rmsnorm")  # auto, promoted on the faked v5e
    with pytest.raises(RuntimeError):
        active_kernel_backends()
    assert not use_pallas("moe_dispatch")  # auto -> xla on a TPU: no kernel, no probe


def test_family_out_of_the_table_resolves_to_xla_on_a_v5e(monkeypatch):
    """Demotion is by the table: on a device that reports what a v5e reports, the two
    families Mosaic refuses resolve to xla through the real detection path, the others to
    pallas; and a TPU whose kind cannot be read is an error, not the generic row."""
    from dolomite_engine_tpu.ops.pallas import config as kernel_config_module

    class _Device:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device()])
    kernel_config_module._reset_platform_cache()
    try:
        assert kernel_config_module._detect_platform_key() == "tpu:v5e"
        assert resolved_kernel_backend("paged_attention") is KernelBackend.xla
        assert resolved_kernel_backend("prefill_attention") is KernelBackend.xla
        assert resolved_kernel_backend("fused_rope_qkv") is KernelBackend.pallas
        assert active_kernel_backends()["paged_attention"] == "xla"

        kernel_config_module._reset_platform_cache()
        monkeypatch.setattr(jax, "devices", lambda *a: [object()])
        with pytest.raises(AttributeError, match="device_kind"):
            resolved_kernel_backend("rmsnorm")
    finally:
        kernel_config_module._reset_platform_cache()


# ------------------------------------------------------------------- fused rmsnorm


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
def test_fused_rmsnorm_parity(dtype, tol):
    from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 64)).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.1 + 1.0).astype(jnp.float32)
    out = fused_rmsnorm(x, w, 1e-5)
    ref = rmsnorm(x, w, 1e-5)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_fused_rmsnorm_fp32_is_bitwise():
    from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm

    # 21 rows: exercises the row padding (no block size divides it)
    x = jax.random.normal(jax.random.PRNGKey(2), (21, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (32,), jnp.float32)
    # ulp-level, not bitwise: this container's CPU XLA fuses the interpret-mode emulator's
    # rsqrt chain differently from the eager reference for some inputs (2e-7 rel ~ 1-2
    # float32 ulp); the property under test is that the kernel is a drop-in numerical
    # replacement, which agreement to the last unit of precision still demonstrates
    np.testing.assert_allclose(
        np.asarray(fused_rmsnorm(x, w, 1e-5)), np.asarray(rmsnorm(x, w, 1e-5)), rtol=5e-7
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
def test_fused_rmsnorm_residual_pair(dtype, tol):
    from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 48)).astype(dtype)
    r = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 48)).astype(dtype)
    w = jnp.ones((48,), jnp.float32)
    out, stream = fused_rmsnorm(x, w, 1e-5, residual=r)
    np.testing.assert_array_equal(
        np.asarray(stream, np.float32), np.asarray(x + r, np.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(rmsnorm(x + r, w, 1e-5), np.float32),
        atol=tol,
        rtol=tol,
    )


def test_fused_rmsnorm_backward_matches_xla():
    from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(6), (5, 3, 32), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(7), (5, 3, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(8), (32,), jnp.float32)

    def fused(x, r, w):
        out, stream = fused_rmsnorm(x, w, 1e-5, residual=r)
        return jnp.sum(out**2) + jnp.sum(stream**3)

    def reference(x, r, w):
        s = x + r
        return jnp.sum(rmsnorm(s, w, 1e-5) ** 2) + jnp.sum(s**3)

    g_fused = jax.grad(fused, argnums=(0, 1, 2))(x, r, w)
    g_ref = jax.grad(reference, argnums=(0, 1, 2))(x, r, w)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_norm_module_fused_matches_xla_through_model():
    """Whole-model check: a gpt_dolomite forward with the rmsnorm family on Pallas
    matches the XLA forward at fp32 tolerance. (Standalone the kernel is bitwise — see
    above — but inside the model XLA fuses the norm with its neighbours and may
    reassociate the mean reduction, so model-level parity is ~1e-7, not exact.)"""
    config, model, params = _make_model()
    ids = jnp.asarray(np.random.RandomState(0).randint(3, 96, (2, 12)), jnp.int32)
    ref = model.apply({"params": params}, ids).logits
    with kernel_overrides(rmsnorm="pallas"):
        out = model.apply({"params": params}, ids).logits
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------- paged attention


def _paged_fixtures(seed=0, num_slots=4, width=1, q_heads=8, kv_heads=2, head_dim=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    num_pages, max_pages = 16, 4
    q = jax.random.normal(ks[0], (num_slots, width, q_heads, head_dim), jnp.float32)
    k_pages = jax.random.normal(ks[1], (num_pages, PAGE, kv_heads, head_dim), jnp.float32)
    v_pages = jax.random.normal(ks[2], (num_pages, PAGE, kv_heads, head_dim), jnp.float32)
    # ragged frontiers; row 1 is an IDLE slot: all-trash table, length 0
    table = np.zeros((num_slots, max_pages), np.int32)
    lengths = np.array([10, 0, 3 * PAGE + 7, 3], np.int32)[:num_slots]
    table[0, :2] = [1, 2]
    table[2, :4] = [3, 4, 5, 6]
    table[3, :1] = [7]
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lengths)


def _paged_reference(q, k_pages, v_pages, table, lengths, scale):
    """The XLA path `_update_paged_kv_cache` lowers to: gather the page view, mask the
    per-row frontier (+ the in-flight window), eager fp32-softmax attention."""
    width = q.shape[1]
    view_len = table.shape[1] * PAGE
    valid = jnp.arange(view_len)[None, :] < (lengths[:, None] + width)
    mask = make_attention_mask(
        q.shape[0], width, view_len, causal=True,
        attention_mask=valid.astype(jnp.int32), query_offset=lengths,
    )
    return eager_attention(
        q, paged_gather_kv(k_pages, table), paged_gather_kv(v_pages, table),
        mask, None, scale,
    )


@pytest.mark.parametrize("width", [1, 4])  # decode and the speculative K+1 window
def test_paged_decode_kernel_parity(width):
    from dolomite_engine_tpu.ops.pallas.paged_attention import paged_decode_attention

    q, k_pages, v_pages, table, lengths = _paged_fixtures(width=width)
    scale = q.shape[-1] ** -0.5
    out = paged_decode_attention(q, k_pages, v_pages, table, lengths, scale)
    ref = _paged_reference(q, k_pages, v_pages, table, lengths, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_paged_decode_kernel_mha_and_under_jit():
    from dolomite_engine_tpu.ops.pallas.paged_attention import paged_decode_attention

    q, k_pages, v_pages, table, lengths = _paged_fixtures(seed=1, q_heads=4, kv_heads=4)
    scale = q.shape[-1] ** -0.5
    out = jax.jit(
        lambda *a: paged_decode_attention(*a, softmax_scale=scale)
    )(q, k_pages, v_pages, table, lengths)
    ref = _paged_reference(q, k_pages, v_pages, table, lengths, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_paged_attention_step_updates_cache_identically():
    """The kernel path's scatter must leave the page pool bit-identical to the XLA
    path's, so committed/rolled-back state can never depend on the backend."""
    from dolomite_engine_tpu.models.modeling_utils import (
        _paged_pallas_attention,
        _update_paged_kv_cache,
    )

    q, k_pages, v_pages, table, lengths = _paged_fixtures(seed=2, width=2)
    new_k = jax.random.normal(jax.random.PRNGKey(9), (4, 2, 2, 16), jnp.float32)
    new_v = jax.random.normal(jax.random.PRNGKey(10), (4, 2, 2, 16), jnp.float32)
    cache = {"k": k_pages, "v": v_pages, "page_table": table}

    _, _, xla_cache, _, _ = _update_paged_kv_cache(new_k, new_v, dict(cache), lengths, None)
    _, kernel_cache = _paged_pallas_attention(q, new_k, new_v, dict(cache), lengths, 0.25)
    np.testing.assert_array_equal(np.asarray(xla_cache["k"]), np.asarray(kernel_cache["k"]))
    np.testing.assert_array_equal(np.asarray(xla_cache["v"]), np.asarray(kernel_cache["v"]))


# ------------------------------------------------------------------- prefill attention


def _prefill_fixtures(seed=0, num_rows=2, width=24, q_heads=8, kv_heads=2, head_dim=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    num_pages, max_pages = 16, 4
    q = jax.random.normal(ks[0], (num_rows, width, q_heads, head_dim), jnp.float32)
    k_pages = jax.random.normal(ks[1], (num_pages, PAGE, kv_heads, head_dim), jnp.float32)
    v_pages = jax.random.normal(ks[2], (num_pages, PAGE, kv_heads, head_dim), jnp.float32)
    # ragged chunk starts: row 0 continues a resident prefix mid-page, row 1 starts cold;
    # pages past each row's frontier stay TRASH (0) — the walk must never read them as real
    table = np.zeros((num_rows, max_pages), np.int32)
    table[0, :3] = [1, 2, 3]
    if num_rows > 1:
        table[1, :2] = [4, 5]
    starts = np.array([10, 0], np.int32)[:num_rows]
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(starts)


def _prefill_reference(q, k_pages, v_pages, table, starts, scale):
    """What the XLA chunk path lowers to: gather the view, per-row causal frontier at
    ``start + row``, eager fp32-softmax attention (the chunk's key-side prefix mask is
    redundant with causality for real rows — see `_paged_prefill_eligible`)."""
    width = q.shape[1]
    view_len = table.shape[1] * PAGE
    mask = make_attention_mask(
        q.shape[0], width, view_len, causal=True, query_offset=starts
    )
    return eager_attention(
        q, paged_gather_kv(k_pages, table), paged_gather_kv(v_pages, table),
        mask, None, scale,
    )


@pytest.mark.parametrize("width", [8, 24])  # one q-block and a multi-block chunk
def test_prefill_attention_kernel_parity(width):
    from dolomite_engine_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    q, k_pages, v_pages, table, starts = _prefill_fixtures(width=width)
    scale = q.shape[-1] ** -0.5
    out = paged_prefill_attention(q, k_pages, v_pages, table, starts, scale)
    ref = _prefill_reference(q, k_pages, v_pages, table, starts, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_prefill_attention_kernel_mha_and_under_jit():
    from dolomite_engine_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    q, k_pages, v_pages, table, starts = _prefill_fixtures(seed=1, q_heads=4, kv_heads=4)
    scale = q.shape[-1] ** -0.5
    out = jax.jit(
        lambda *a: paged_prefill_attention(*a, softmax_scale=scale)
    )(q, k_pages, v_pages, table, starts)
    ref = _prefill_reference(q, k_pages, v_pages, table, starts, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_prefill_attention_quantized_pages():
    """The kernel's per-page DMA dequant must match attention over the dequantizing
    gather (`paged_gather_kv_dequant`) on an int8 pool with non-trivial scales."""
    from dolomite_engine_tpu.ops.attention import paged_gather_kv_dequant
    from dolomite_engine_tpu.ops.kv_quant import quantize_pages_xla
    from dolomite_engine_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    q, k_pages, v_pages, table, starts = _prefill_fixtures(seed=2)
    valid = jnp.ones((k_pages.shape[0], PAGE), bool)
    k_q, k_s = quantize_pages_xla(k_pages * 3.0, valid, 127.0, jnp.int8)
    v_q, v_s = quantize_pages_xla(v_pages * 0.5, valid, 127.0, jnp.int8)
    scale = q.shape[-1] ** -0.5
    out = paged_prefill_attention(
        q, k_q, v_q, table, starts, scale, k_scales=k_s, v_scales=v_s
    )
    ref = eager_attention(
        q,
        paged_gather_kv_dequant(k_q, k_s, table, jnp.float32),
        paged_gather_kv_dequant(v_q, v_s, table, jnp.float32),
        make_attention_mask(
            q.shape[0], q.shape[1], table.shape[1] * PAGE, causal=True,
            query_offset=starts,
        ),
        None,
        scale,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_prefill_step_updates_cache_identically():
    """The prefill-kernel path's scatter (incl. the mask-derived pad-to-trash redirect)
    must leave the page pool bit-identical to the XLA chunk path's."""
    from dolomite_engine_tpu.models.modeling_utils import (
        _paged_prefill_pallas_attention,
        _update_paged_kv_cache,
    )

    q, k_pages, v_pages, table, starts = _prefill_fixtures(seed=3, num_rows=1, width=24)
    new_k = jax.random.normal(jax.random.PRNGKey(9), (1, 24, 2, 16), jnp.float32)
    new_v = jax.random.normal(jax.random.PRNGKey(10), (1, 24, 2, 16), jnp.float32)
    cache = {"k": k_pages, "v": v_pages, "page_table": table[:1]}
    start = jnp.asarray(int(starts[0]), jnp.int32)
    # the chunk's key-side mask: resident prefix + 20 real tokens, 4-token pad tail
    mask = np.zeros((1, table.shape[1] * PAGE), np.int32)
    mask[0, : int(starts[0]) + 20] = 1
    mask = jnp.asarray(mask)

    _, _, xla_cache, _, _ = _update_paged_kv_cache(new_k, new_v, dict(cache), start, mask)
    _, kernel_cache = _paged_prefill_pallas_attention(
        q[:1], new_k, new_v, dict(cache), start, mask, 0.25
    )
    np.testing.assert_array_equal(np.asarray(xla_cache["k"]), np.asarray(kernel_cache["k"]))
    np.testing.assert_array_equal(np.asarray(xla_cache["v"]), np.asarray(kernel_cache["v"]))


# ------------------------------------------------------------------- paged kv quant


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_kv_quant_kernel_bytes_identical(kv_dtype):
    """The ``paged_kv_quant`` Pallas encode must be BYTE-identical to the XLA reference
    — pool state can never depend on the backend."""
    from dolomite_engine_tpu.ops.kv_quant import (
        KV_QUANT_DTYPES,
        quantize_pages_xla,
    )
    from dolomite_engine_tpu.ops.pallas.kv_quant import quantize_pages_pallas

    dtype, qmax = KV_QUANT_DTYPES[kv_dtype]
    rs = np.random.RandomState(11)
    values = jnp.asarray(rs.randn(6, PAGE, 2, 8) * 2.0, jnp.float32)
    valid = jnp.asarray(rs.rand(6, PAGE) > 0.3)
    q_ref, s_ref = quantize_pages_xla(values, valid, qmax, dtype)
    q_ker, s_ker = quantize_pages_pallas(values, valid, qmax, dtype)
    np.testing.assert_array_equal(
        np.asarray(q_ref).view(np.uint8), np.asarray(q_ker).view(np.uint8)
    )
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_ker))


def test_paged_kv_quant_scale_ignores_stale_tail():
    """Scales come from the VALID token rows only: garbage beyond the frontier must not
    inflate them (the rollback/trash discipline depends on this)."""
    from dolomite_engine_tpu.ops.kv_quant import quantize_pages_xla

    values = np.ones((1, PAGE, 1, 4), np.float32)
    values[0, PAGE - 1] = 1e6  # stale garbage in the last row
    valid = np.zeros((1, PAGE), bool)
    valid[0, : PAGE - 1] = True
    _, scales = quantize_pages_xla(
        jnp.asarray(values), jnp.asarray(valid), 127.0, jnp.int8
    )
    np.testing.assert_allclose(np.asarray(scales), 1.0 / 127.0, rtol=1e-6)


# ------------------------------------------------------------------- grouped moe


def _moe_fixtures(seed, T=33, d=16, f=24, E=8, k=2, dtype=jnp.float32, bias=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, d)).astype(dtype)
    w_fc = (jax.random.normal(ks[1], (E, d, f)) * 0.1).astype(dtype)
    w_proj = (jax.random.normal(ks[2], (E, f, d)) * 0.1).astype(dtype)
    b_fc = (jax.random.normal(ks[3], (E, f)) * 0.1).astype(dtype) if bias else None
    b_proj = (jax.random.normal(ks[4], (E, d)) * 0.1).astype(dtype) if bias else None
    logits = jax.random.normal(ks[5], (T, E), jnp.float32)
    weights, selected = route(logits, k)
    return x, weights.astype(dtype), selected, w_fc, b_fc, w_proj, b_proj, E


@pytest.mark.parametrize(
    "dtype,tol,bias", [(jnp.float32, 1e-5, True), (jnp.float32, 1e-5, False), (jnp.bfloat16, 1e-2, True)]
)
def test_grouped_moe_dispatch_parity(dtype, tol, bias):
    from dolomite_engine_tpu.ops.pallas.moe import experts_grouped

    x, weights, selected, w_fc, b_fc, w_proj, b_proj, E = _moe_fixtures(
        0, dtype=dtype, bias=bias
    )
    act = jax.nn.gelu
    ref = experts_eager(x, combine_weights(weights, selected, E), w_fc, b_fc, w_proj, b_proj, act)
    out = experts_grouped(x, weights, selected, w_fc, b_fc, w_proj, b_proj, act, E)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_grouped_moe_dispatch_empty_experts():
    """Experts no token routed to must contribute nothing (and not corrupt neighbours):
    force all tokens onto two of eight experts."""
    from dolomite_engine_tpu.ops.pallas.moe import experts_grouped

    x, _, _, w_fc, b_fc, w_proj, b_proj, E = _moe_fixtures(1, T=12)
    selected = jnp.asarray(np.tile([[2, 5]], (12, 1)), jnp.int32)
    weights = jnp.full((12, 2), 0.5, jnp.float32)
    act = jax.nn.gelu
    ref = experts_eager(x, combine_weights(weights, selected, E), w_fc, b_fc, w_proj, b_proj, act)
    out = experts_grouped(x, weights, selected, w_fc, b_fc, w_proj, b_proj, act, E)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_grouped_moe_backward_matches_eager():
    from dolomite_engine_tpu.ops.pallas.moe import experts_grouped

    x, weights, selected, w_fc, b_fc, w_proj, b_proj, E = _moe_fixtures(2)
    act = jax.nn.gelu

    def loss_grouped(x, w_fc, w_proj):
        return jnp.sum(
            experts_grouped(x, weights, selected, w_fc, b_fc, w_proj, b_proj, act, E) ** 2
        )

    def loss_eager(x, w_fc, w_proj):
        combine = combine_weights(weights, selected, E)
        return jnp.sum(experts_eager(x, combine, w_fc, b_fc, w_proj, b_proj, act) ** 2)

    g_grouped = jax.grad(loss_grouped, argnums=(0, 1, 2))(x, w_fc, w_proj)
    g_eager = jax.grad(loss_eager, argnums=(0, 1, 2))(x, w_fc, w_proj)
    for a, b in zip(g_grouped, g_eager):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_ep_local_compute_rides_grouped_kernel():
    """`experts_ep_a2a`'s local body (rows tagged with local expert ids + the dummy
    empty-slot id) must produce the same output on both backends."""
    from dolomite_engine_tpu.ops.moe import _local_expert_compute

    rs = np.random.RandomState(3)
    num_local, rows, d, f = 3, 20, 8, 12
    x = jnp.asarray(rs.randn(rows, d).astype(np.float32))
    # include dummy slots (id == num_local) and an expert with zero rows (id 1 unused)
    expert_ids = jnp.asarray(rs.choice([0, 2, num_local], size=rows).astype(np.int32))
    w_fc = jnp.asarray(rs.randn(num_local, d, f).astype(np.float32) * 0.1)
    w_proj = jnp.asarray(rs.randn(num_local, f, d).astype(np.float32) * 0.1)
    b_fc = jnp.asarray(rs.randn(num_local, f).astype(np.float32) * 0.1)
    b_proj = jnp.asarray(rs.randn(num_local, d).astype(np.float32) * 0.1)

    args = (x, expert_ids, w_fc, b_fc, w_proj, b_proj, jax.nn.gelu, num_local)
    ref = _local_expert_compute(*args)
    with kernel_overrides(moe_dispatch="pallas"):
        out = _local_expert_compute(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    # dummy rows are exactly zero on both backends
    dummy = np.asarray(expert_ids) == num_local
    assert np.all(np.asarray(out)[dummy] == 0.0)


def test_moe_model_forward_parity_with_kernels():
    from dolomite_engine_tpu.models import config_from_dict, get_model_class

    config = config_from_dict(
        dict(
            model_type="moe_dolomite", vocab_size=96, n_positions=64, n_embd=32,
            n_layer=2, n_head=4, num_key_value_heads=2, attention_head_type="gqa",
            position_embedding_type="rope", add_bias=True, activation_function="swiglu",
            normalization_function="rmsnorm", resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0, num_experts=4, num_experts_per_tok=2,
            router_aux_loss_coef=0.01,
        )
    )
    model = get_model_class("moe_dolomite")(config=config, moe_implementation="eager")
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    ref = model.apply({"params": params}, ids).logits
    with kernel_overrides(moe_dispatch="pallas", rmsnorm="pallas"):
        out = model.apply({"params": params}, ids).logits
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------- serving engine


def _make_model(vocab=96, layers=2, seed=0):
    config = CommonConfig(
        vocab_size=vocab, n_positions=512, n_embd=32, n_layer=layers, n_head=4,
        num_key_value_heads=2, attention_head_type="gqa", position_embedding_type="rope",
        add_bias=False, activation_function="swiglu", normalization_function="rmsnorm",
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        bos_token_id=0, eos_token_id=1, pad_token_id=2,
    )
    model = GPTDolomiteForCausalLM(config=config)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return config, model, params


def _expected(model, params, config, prompt, rng, max_new):
    ids = jnp.asarray([prompt], jnp.int32)
    out, _ = generate_tokens(
        model, params, ids, jnp.ones_like(ids), rng, max_new_tokens=max_new,
        do_sample=False, pad_token_id=config.pad_token_id,
    )
    return [int(t) for t in np.asarray(out[0])]


def test_engine_paged_kernel_parity_and_compile_once():
    """Acceptance: with the ``paged_attention`` kernel enabled, the engine stays
    token-for-token equal to `generate_tokens` (XLA reference) with paged KV + prefix
    cache + chunked prefill active, and the one-compile decode invariant holds."""
    config, model, params = _make_model()
    rs = np.random.RandomState(3)
    shared = list(map(int, rs.randint(3, config.vocab_size, 2 * PAGE)))
    prompts = [
        shared + list(map(int, rs.randint(3, config.vocab_size, 5))),
        list(map(int, rs.randint(3, config.vocab_size, 41))),
        shared + list(map(int, rs.randint(3, config.vocab_size, 9))),
    ]
    rngs = [jax.random.PRNGKey(100 + i) for i in range(3)]
    max_new = 12

    with kernel_overrides(paged_attention="pallas"):
        engine = ServingEngine(
            model, params, num_slots=2, max_len=128, prefill_bucket_multiple=8,
            eos_token_id=None, pad_token_id=config.pad_token_id,
            page_size=PAGE, prefill_chunk_tokens=16,
        )
        states = [
            engine.submit(prompt_ids=p, max_new_tokens=max_new, rng=r)
            for p, r in zip(prompts, rngs)
        ]
        engine.drain()
        assert engine.decode_compiles == 1
        assert engine.stats.prefix_hit_tokens > 0

    for i, state in enumerate(states):
        assert state.tokens == _expected(
            model, params, config, prompts[i], rngs[i], max_new
        ), f"request {i} diverged"


def test_engine_paged_kernel_parity_with_speculation():
    """Same acceptance with the speculative K+1 verify window riding the kernel: n-gram
    drafting on, verify compiles once, tokens identical to the XLA sequential path."""
    config, model, params = _make_model()
    rs = np.random.RandomState(5)
    prompts = [
        (list(map(int, rs.randint(3, config.vocab_size, 6))) * 6)[:30],
        list(map(int, rs.randint(3, config.vocab_size, 21))),
    ]
    rngs = [jax.random.PRNGKey(200 + i) for i in range(2)]
    max_new = 16

    with kernel_overrides(paged_attention="pallas"):
        engine = ServingEngine(
            model, params, num_slots=2, max_len=96, prefill_bucket_multiple=8,
            eos_token_id=None, pad_token_id=config.pad_token_id, page_size=PAGE,
            prefill_chunk_tokens=16, speculate_ngram=True, draft_k=4,
        )
        states = [
            engine.submit(prompt_ids=p, max_new_tokens=max_new, rng=r)
            for p, r in zip(prompts, rngs)
        ]
        engine.drain()
        assert engine.verify_compiles == 1
        assert engine.decode_compiles == 0
        assert engine.stats.draft_tokens_accepted > 0  # the K+1 window actually ran

    for i, state in enumerate(states):
        assert state.tokens == _expected(
            model, params, config, prompts[i], rngs[i], max_new
        ), f"request {i} diverged"


def test_engine_prefill_kernel_parity_and_compile_once():
    """Acceptance: with the ``prefill_attention`` kernel enabled, chunked prefill stays
    token-for-token equal to `generate_tokens` (XLA reference) with paged KV + prefix
    cache + chunked prefill active, and the one-compile decode invariant holds — prefill
    was the last attention path still on the worst-case gathered view."""
    config, model, params = _make_model()
    rs = np.random.RandomState(7)
    shared = list(map(int, rs.randint(3, config.vocab_size, 2 * PAGE)))
    prompts = [
        shared + list(map(int, rs.randint(3, config.vocab_size, 5))),
        list(map(int, rs.randint(3, config.vocab_size, 41))),
        shared + list(map(int, rs.randint(3, config.vocab_size, 9))),
    ]
    rngs = [jax.random.PRNGKey(300 + i) for i in range(3)]
    max_new = 12

    with kernel_overrides(prefill_attention="pallas"):
        engine = ServingEngine(
            model, params, num_slots=2, max_len=128, prefill_bucket_multiple=8,
            eos_token_id=None, pad_token_id=config.pad_token_id,
            page_size=PAGE, prefill_chunk_tokens=16,
        )
        states = [
            engine.submit(prompt_ids=p, max_new_tokens=max_new, rng=r)
            for p, r in zip(prompts, rngs)
        ]
        engine.drain()
        assert engine.decode_compiles == 1
        assert engine.stats.prefix_hit_tokens > 0

    for i, state in enumerate(states):
        assert state.tokens == _expected(
            model, params, config, prompts[i], rngs[i], max_new
        ), f"request {i} diverged"


def test_engine_quantized_kernels_match_quantized_xla():
    """With an int8 pool, the full kernel stack (paged_attention + prefill_attention +
    paged_kv_quant on Pallas) must reproduce the quantized XLA reference path
    token-for-token: the quantize-on-scatter is shared, so the only difference is where
    dequantization happens — and that is a pure read."""
    config, model, params = _make_model()
    rs = np.random.RandomState(9)
    prompts = [
        list(map(int, rs.randint(3, config.vocab_size, 37))),
        list(map(int, rs.randint(3, config.vocab_size, 21))),
    ]
    rngs = [jax.random.PRNGKey(400 + i) for i in range(2)]

    def run():
        engine = ServingEngine(
            model, params, num_slots=2, max_len=96, prefill_bucket_multiple=8,
            eos_token_id=None, pad_token_id=config.pad_token_id,
            page_size=PAGE, prefill_chunk_tokens=16, kv_dtype="int8",
        )
        states = [
            engine.submit(prompt_ids=p, max_new_tokens=10, rng=r)
            for p, r in zip(prompts, rngs)
        ]
        engine.drain()
        assert engine.decode_compiles == 1
        return [s.tokens for s in states]

    xla_tokens = run()
    with kernel_overrides(
        paged_attention="pallas", prefill_attention="pallas", paged_kv_quant="pallas"
    ):
        kernel_tokens = run()
    assert kernel_tokens == xla_tokens


# ------------------------------------------------------------------- telemetry


def test_kernel_backends_in_telemetry_records(tmp_path):
    from dolomite_engine_tpu.utils.telemetry import (
        Telemetry,
        install_telemetry,
        uninstall_telemetry,
    )

    config, model, params = _make_model()
    sink = tmp_path / "kernels.jsonl"
    with kernel_overrides(paged_attention="pallas", rmsnorm="pallas"):
        telemetry = Telemetry(sink_path=str(sink), rank=0)
        install_telemetry(telemetry)
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_len=64, prefill_bucket_multiple=8,
                eos_token_id=None, pad_token_id=config.pad_token_id, page_size=PAGE,
            )
            engine.submit(prompt_ids=[5, 6, 7, 8], max_new_tokens=4)
            engine.drain()
            telemetry.close()
        finally:
            uninstall_telemetry()

    records = [json.loads(line) for line in open(sink)]
    run_start = next(r for r in records if r["kind"] == "run_start")
    serving = [r for r in records if r["kind"] == "serving"][-1]
    expected = {
        "splash_attention": "xla", "paged_attention": "pallas",
        "prefill_attention": "xla", "paged_kv_quant": "xla",
        "rmsnorm": "pallas", "moe_dispatch": "xla",
        "fused_ce": "xla", "fused_rope_qkv": "xla",
    }
    assert run_start["kernels"] == expected
    assert serving["kernels"] == expected

    # and the summary tool renders a kernels line from it
    from tools.telemetry_summary import summarize

    text = summarize(records)
    assert "pallas [paged_attention, rmsnorm]" in text


# ------------------------------------------------------------------- fused_ce


def _ce_fixtures(seed=0, B=2, S=24, H=32, V=211):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    hidden = jax.random.normal(ks[0], (B, S, H), jnp.float32)
    table = jax.random.normal(ks[1], (V, H), jnp.float32) * 0.05
    labels = jax.random.randint(ks[2], (B, S), 0, V)
    labels = labels.at[0, :5].set(-100)  # IGNORE_INDEX rows must not contribute
    return hidden, table, labels


# (B, S, V, chunk) -> the (token_blocks, vocab_tiles) `plan_loss_backward` makes of them:
# the shapes that choose different loop orders for the backward rule
_CE_TILINGS = {
    "few_tokens_one_block_padded_vocab": ((2, 24, 211, 7), (1, 4)),  # T 56 < V 211 = 4 x 53 - 1
    "blocks_and_tiles": ((2, 64, 96, 8), (4, 2)),  # T 128 > V 96
    "blocks_and_tiles_padded_vocab": ((2, 64, 97, 8), (4, 2)),  # ... and 97 = 2 x 49 - 1
    "many_tokens_one_tile": ((4, 64, 32, 8), (8, 1)),  # T 256 >> V 32: the table is carried
}
# what the configuration may state besides the shapes; fp32 rows keep the ulp tolerances
_CE_NUMERICS = {
    "fp32": dict(),
    "fp32_logit_scale": dict(logit_scale=0.125),
    "bf16_logit_scale": dict(compute_dtype=jnp.bfloat16, logit_scale=0.5),
    "bf16_no_upcast": dict(compute_dtype=jnp.bfloat16, upcast=False),
}


@pytest.mark.parametrize("numerics", list(_CE_NUMERICS))
@pytest.mark.parametrize("z_coef", [0.0, 1e-3])
@pytest.mark.parametrize("tiling", list(_CE_TILINGS))
def test_fused_ce_chunked_matches_unchunked_to_ulp(tiling, z_coef, numerics):
    """Acceptance: chunked-vs-unchunked loss AND grads within 1-2 float32 ulp, with the
    per-chunk reduction on the XLA reference and on the fused_ce kernel — over the shapes
    that make the per-token rule's backward choose each loop order (one token block or
    several, one vocabulary tile or several, a vocabulary no tile count divides), with
    IGNORE_INDEX rows, z-loss on and off, `logit_scale`, bf16 operands and `upcast` both
    ways (the bf16 rows within 2 bf16 ulp of the gradient's largest entry: the unchunked
    reference rounds its own gradients to bf16). Since PR 39 the weightless call's
    gradients leave its differentiated forward (one block of kept logits at these sizes,
    XLA's on either backend); the backend's scan serves its undifferentiated value, and
    the tiled backward the same loss with unit weights."""
    from dolomite_engine_tpu.ops.loss import (
        causal_lm_loss, fused_linear_cross_entropy, plan_loss_backward,
    )

    (B, S, V, chunk), expected = _CE_TILINGS[tiling]
    options = dict(compute_dtype=jnp.float32, logit_scale=None, upcast=True)
    options.update(_CE_NUMERICS[numerics])
    dtype, scale, upcast = options["compute_dtype"], options["logit_scale"], options["upcast"]
    hidden, table, labels = _ce_fixtures(B=B, S=S, V=V)
    plan = plan_loss_backward(B, -(-S // chunk), chunk, V, hidden.shape[-1])[0]
    assert (plan.token_blocks, plan.vocab_tiles) == expected

    def unchunked(h, t):
        logits = jnp.dot(h.astype(dtype), t.astype(dtype).T)
        if scale is not None:
            logits = logits * scale
        return causal_lm_loss(
            logits, jnp.zeros((B, S), jnp.int32), labels=labels, z_loss_coef=z_coef,
            upcast=upcast,
        )

    def chunked(h, t):
        return fused_linear_cross_entropy(
            h, t, labels, chunk_size=chunk, z_loss_coef=z_coef, **options
        )

    ref_loss, ref_grads = jax.value_and_grad(unchunked, argnums=(0, 1))(hidden, table)
    fp32 = dtype == jnp.float32
    # loss: summation-order only -> 1-2 fp32 ulp around ~5.3 (bf16 logits without
    # upcast: the unchunked loss itself is a bf16 sum)
    loss_atol = 2e-6 if upcast else 0.1
    for backend in ("xla", "pallas"):
        with kernel_overrides(fused_ce=backend):
            loss, grads = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, table)
            undifferentiated = chunked(hidden, table)
            unit_loss, unit_grads = jax.value_and_grad(
                lambda h, t: fused_linear_cross_entropy(
                    h, t, labels, chunk_size=chunk, z_loss_coef=z_coef, weights=jnp.ones((B, S)), **options
                ),
                argnums=(0, 1),
            )(hidden, table)
        for value in (loss, undifferentiated, unit_loss):
            np.testing.assert_allclose(float(value), float(ref_loss), rtol=0, atol=loss_atol)
        for g, r in zip(grads + unit_grads, ref_grads + ref_grads):
            # same atol style as the remat-policy matrix: ~1 fp32 ulp at magnitude 1
            atol = 1.2e-7 if fp32 else 2 * 2.0**-7 * float(jnp.max(jnp.abs(r)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0, atol=atol)


def test_fused_ce_kernel_rowwise_terms():
    from dolomite_engine_tpu.ops.loss import cross_entropy_terms
    from dolomite_engine_tpu.ops.pallas.fused_ce import fused_ce_chunk

    hidden, table, labels = _ce_fixtures(seed=4, V=203)  # odd vocab: exercises tiles
    logits = jnp.dot(hidden, table.T)
    ref = cross_entropy_terms(logits, labels, want_z=True)
    out = fused_ce_chunk(
        hidden, table, labels, logit_scale=None, upcast=True, compute_dtype=jnp.float32
    )
    for a, b in zip(out, ref):
        np.testing.assert_allclose(float(a), float(b), rtol=3e-7)


def test_fused_ce_logit_scale_and_bf16_compute():
    from dolomite_engine_tpu.ops.loss import cross_entropy_terms
    from dolomite_engine_tpu.ops.pallas.fused_ce import fused_ce_chunk

    hidden, table, labels = _ce_fixtures(seed=5)
    scale = 0.125
    logits = (jnp.dot(hidden.astype(jnp.bfloat16), table.astype(jnp.bfloat16).T) * scale)
    ref = cross_entropy_terms(logits, labels, upcast=True, want_z=True)
    out = fused_ce_chunk(
        hidden, table, labels, logit_scale=scale, upcast=True,
        compute_dtype=jnp.bfloat16,
    )
    np.testing.assert_allclose(float(out[0]), float(ref[0]), rtol=2e-2)
    np.testing.assert_allclose(float(out[2]), float(ref[2]), rtol=0)


def _fused_loss_configs(base):
    import dataclasses

    fused = dataclasses.replace(base, fused_lm_head_loss=True, loss_chunk_size=8)
    return base, fused


def test_fused_ce_model_packed_z_loss_parity():
    """The model's fused-loss path (packed segment-ids + z-loss) matches the
    full-logits path, XLA and Pallas chunk backends alike."""
    import dataclasses

    config, model, params = _make_model()
    config_z = dataclasses.replace(config, z_loss_coef=1e-3)
    plain, fused = _fused_loss_configs(config_z)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(3, config.vocab_size, (2, 16)), jnp.int32)
    # packed padding-free batch: two documents per row via segment ids
    segment_ids = jnp.asarray([[1] * 7 + [2] * 9, [1] * 12 + [2] * 4], jnp.int32)
    position_ids = jnp.asarray(
        [list(range(7)) + list(range(9)), list(range(12)) + list(range(4))], jnp.int32
    )

    def loss_for(cfg, backend):
        m = GPTDolomiteForCausalLM(config=cfg)
        with kernel_overrides(fused_ce=backend):
            return float(
                m.apply(
                    {"params": params}, ids, position_ids=position_ids,
                    segment_ids=segment_ids, compute_loss=True,
                ).loss
            )

    ref = loss_for(plain, "xla")
    assert ref == pytest.approx(loss_for(fused, "xla"), abs=2e-6)
    assert ref == pytest.approx(loss_for(fused, "pallas"), abs=2e-6)


def test_fused_ce_moe_aux_loss_combination():
    """moe_dolomite: fused CE + the router aux loss combine identically to the
    full-logits path (aux is added after the CE term in both)."""
    import dataclasses

    from dolomite_engine_tpu.models.config import MoEConfig
    from dolomite_engine_tpu.models.moe_dolomite import MoEDolomiteForCausalLM

    config = MoEConfig(
        vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        num_key_value_heads=2, attention_head_type="gqa", position_embedding_type="rope",
        add_bias=False, activation_function="swiglu", normalization_function="rmsnorm",
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, num_experts=4,
        num_experts_per_tok=2, router_aux_loss_coef=0.02, z_loss_coef=1e-3,
    )
    model = MoEDolomiteForCausalLM(config=config, moe_implementation="eager")
    ids = jnp.asarray(np.random.RandomState(1).randint(3, 96, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    out_ref = model.apply({"params": params}, ids, compute_loss=True)
    fused_cfg = dataclasses.replace(config, fused_lm_head_loss=True, loss_chunk_size=8)
    for backend in ("xla", "pallas"):
        fused_model = MoEDolomiteForCausalLM(config=fused_cfg, moe_implementation="eager")
        with kernel_overrides(fused_ce=backend):
            out = fused_model.apply({"params": params}, ids, compute_loss=True)
        assert float(out.aux_loss) == float(out_ref.aux_loss)  # same aux either way
        np.testing.assert_allclose(float(out.loss), float(out_ref.loss), rtol=0, atol=2e-6)


def _loop_carries(jaxpr) -> list:
    """[(primitive, trip count or None, [carry avals])] of every scan / while in `jaxpr`,
    nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            first = eqn.params["num_consts"]
            carry = eqn.invars[first : first + eqn.params["num_carry"]]
            found.append(("scan", eqn.params["length"], [v.aval for v in carry]))
        elif eqn.primitive.name == "while":
            first = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            found.append(("while", None, [v.aval for v in eqn.invars[first:]]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_loop_carries(inner))
    return found


@pytest.mark.parametrize("V, blocks", [(1999, 1), (199, 2)], ids=["one_block", "two_blocks"])
def test_fused_ce_peak_logits_memory_is_o_chunk(V, blocks):
    """Acceptance: the rules that stay chunked — the undifferentiated call and the
    per-token rule, forward and backward — never materialize a [B*S, V]-sized logits buffer,
    asserted through the shared perf-signature HLO-feature API (utils/program_signature.py,
    the same checks `tools/perf_ledger.py` gates on): the unchunked grad program must
    contain the full [B, S, V] tile, the chunked ones must not (at most the forward's
    [B, chunk, V] scan tile and the backward rule's [chunks a block, B, chunk, shards, tile
    rows] tile, no larger) — and no loop of the backward program carries a table-shaped
    float32 buffer where one token block holds all tokens; where there are several, one
    loop does, once a block, not once a chunk. The summed rule's differentiated program
    (PR 39) keeps ONE token block's logits, [chunks a block, B, chunk, V] under a budget in
    bytes that a toy never reaches, and neither a chunk tile nor a vocabulary tile beside it;
    with one block no loop is left in it at all."""
    from dolomite_engine_tpu.ops.loss import (
        causal_lm_loss, fused_linear_cross_entropy, plan_loss_backward, plan_loss_blocks,
    )
    from dolomite_engine_tpu.utils.program_signature import capture_program_signature

    B, S, H = 2, 64, 16
    hidden = jax.random.normal(jax.random.PRNGKey(0), (B, S, H), jnp.float32)
    table = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    chunk = 8
    plan = plan_loss_backward(B, S // chunk, chunk, V, H)[0]
    assert plan.token_blocks == blocks
    tile = plan.logits_tile(B, S // chunk, chunk)
    assert np.prod(tile) <= 1.1 * B * chunk * V  # the forward's budget (a padded tile's worth over)
    kept = plan_loss_blocks(B, S // chunk, chunk, V, H, 4)[0]
    assert kept.token_blocks == 1  # 0.5 MB of the 384 MiB a block may keep

    def unchunked(h, t):
        return causal_lm_loss(jnp.dot(h, t.T), jnp.zeros((B, S), jnp.int32), labels=labels)

    def chunked(h, t, weights=None):
        return fused_linear_cross_entropy(
            h, t, labels, chunk_size=chunk, compute_dtype=jnp.float32, weights=weights
        )

    def per_token(h, t):
        return chunked(h, t, jnp.ones((B, S), jnp.float32))

    checks = {
        "full_logits": ((B, S, V), "f32"),
        "chunk_logits": ((B, chunk, V), "f32"),
        "tile_logits": (tile, "f32"),
        "block_logits": (kept.logits_block(B, S // chunk, chunk, V), "f32"),
    }
    # forward AND backward: grad of the loss is where remat pressure lives.
    # compile=False: the assertion is about the lowering, not the buffer assignment
    sig_unchunked = capture_program_signature(
        jax.grad(unchunked, argnums=(0, 1)), hidden, table,
        name="ce_unchunked_grad", compile=False, shape_checks=checks,
    )
    sig_per_token = capture_program_signature(
        jax.grad(per_token, argnums=(0, 1)), hidden, table,
        name="ce_per_token_grad", compile=False, shape_checks=checks,
    )
    sig_undifferentiated = capture_program_signature(
        chunked, hidden, table, name="ce_chunked", compile=False, shape_checks=checks,
    )
    sig_summed = capture_program_signature(
        jax.grad(chunked, argnums=(0, 1)), hidden, table,
        name="ce_summed_grad", compile=False, shape_checks=checks,
    )
    assert sig_unchunked.hlo["checks"]["full_logits"]  # the reference builds full logits
    assert not sig_unchunked.hlo["checks"]["tile_logits"]
    assert not sig_per_token.hlo["checks"]["full_logits"]
    assert sig_per_token.hlo["checks"]["chunk_logits"]  # ...while the forward's chunk tile
    assert sig_per_token.hlo["checks"]["tile_logits"]  # and the backward's vocabulary tile exist
    assert not sig_per_token.hlo["checks"]["block_logits"]
    assert sig_undifferentiated.hlo["checks"] == {
        "full_logits": False, "chunk_logits": True, "tile_logits": False, "block_logits": False,
    }
    # ("chunk_logits" is blind here: the check is textual, and a chunk's shape is the tail of
    # the block's [chunks, B, chunk, V]; the product count in tests/ops/test_ops.py is not)
    summed = sig_summed.hlo["checks"]
    assert summed["block_logits"] and not summed["full_logits"] and not summed["tile_logits"]

    loops = _loop_carries(jax.make_jaxpr(jax.grad(per_token, argnums=(0, 1)))(hidden, table).jaxpr)
    assert len(loops) >= 2  # the forward's scan and the backward's
    table_sized = [
        (kind, trips) for kind, trips, carry in loops
        if any(a.dtype == jnp.float32 and a.size >= V * H for a in carry)
    ]
    # one block: every tile's table gradient leaves its matmul once (the scan's ys);
    # two blocks: the outer loop alone carries it, for 2 trips and not S // chunk = 8
    assert table_sized == ([] if blocks == 1 else [("scan", blocks)])
    assert not _loop_carries(jax.make_jaxpr(jax.grad(chunked, argnums=(0, 1)))(hidden, table).jaxpr)


# ------------------------------------------------------------------- fused_rope_qkv


def _rope_qkv_fixtures(hq, hkv, D=16, B=2, S=9, yarn=False, seed=0):
    from dolomite_engine_tpu.ops.rope import RoPEParams, get_cos_sin

    scaling = (
        {"type": "yarn", "factor": 4.0, "original_max_position_embeddings": 8}
        if yarn
        else None
    )
    rope = RoPEParams.from_config(D, rope_scaling=scaling)
    # per-row offsets: the serving decode/verify shape (every slot at its own position)
    pos = jnp.arange(S)[None, :] + jnp.asarray([[0], [3]])[:B]
    cos, sin = get_cos_sin(rope, pos)
    qkv = jax.random.normal(jax.random.PRNGKey(seed), (B, S, (hq + 2 * hkv) * D), jnp.float32)
    return qkv, cos, sin


@pytest.mark.parametrize("head_type,hq,hkv", [("mha", 4, 4), ("gqa", 4, 2), ("mqa", 4, 1)])
@pytest.mark.parametrize("yarn", [False, True])
def test_fused_rope_qkv_parity(head_type, hq, hkv, yarn):
    from dolomite_engine_tpu.ops.rope import split_qkv_apply_rope

    D = 16
    qkv, cos, sin = _rope_qkv_fixtures(hq, hkv, D=D, yarn=yarn)
    q0, k0, v0 = split_qkv_apply_rope(qkv, hq, hkv, D, (cos, sin))
    with kernel_overrides(fused_rope_qkv="pallas"):
        q1, k1, v1 = split_qkv_apply_rope(qkv, hq, hkv, D, (cos, sin))
    # V blocks pass through untouched -> bitwise; Q/K at 1-2 fp32 ulp (the two
    # lowerings contract the multiply-add chain differently)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_allclose(np.asarray(q0), np.asarray(q1), rtol=0, atol=5e-7)
    np.testing.assert_allclose(np.asarray(k0), np.asarray(k1), rtol=0, atol=5e-7)


def test_fused_rope_qkv_backward_matches_xla():
    from dolomite_engine_tpu.ops.rope import split_qkv_apply_rope

    hq, hkv, D = 4, 2, 16
    qkv, cos, sin = _rope_qkv_fixtures(hq, hkv, D=D, yarn=True, seed=3)

    def loss(x, backend):
        with kernel_overrides(fused_rope_qkv=backend):
            q, k, v = split_qkv_apply_rope(x, hq, hkv, D, (cos, sin))
        return jnp.sum(q**2) + 0.5 * jnp.sum(k**2) + jnp.sum(v**3)

    g_ref = jax.grad(lambda x: loss(x, "xla"))(qkv)
    g_ker = jax.grad(lambda x: loss(x, "pallas"))(qkv)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_ker), rtol=0, atol=1e-6)


def test_fused_rope_qkv_through_model_and_jit():
    """Whole-model check through the ONE shared call site: a gpt_dolomite forward
    (training shape) and a jitted decode-shaped call both match XLA with the kernel
    on."""
    config, model, params = _make_model()
    ids = jnp.asarray(np.random.RandomState(0).randint(3, 96, (2, 12)), jnp.int32)
    ref = model.apply({"params": params}, ids).logits
    with kernel_overrides(fused_rope_qkv="pallas"):
        out = jax.jit(lambda p, i: model.apply({"params": p}, i).logits)(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------- kernels under a mesh


def test_kernels_run_per_shard_under_a_mesh(mesh_2x2x2):
    """A sharded train step with the training-path families on Pallas: the TPU refuses a
    bare `pallas_call` under GSPMD ("Mosaic kernels cannot be automatically
    partitioned"), so rmsnorm and rope+QKV must trace as `shard_map`s — also in the
    backward pass and inside remat, where the model's rules context is gone — and still
    match the XLA lowering in loss and gradients. GQA at tp=2 puts the K/V boundary
    inside the second shard, so the per-shard rope width is exercised too."""
    from dolomite_engine_tpu.enums import Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining

    text = jnp.asarray(np.random.RandomState(0).randint(3, 96, (4, 33)), jnp.int32)

    def loss_and_grad():
        # a fresh wrapper a side: jit and remat cache traces by function and shapes, not
        # by kernel selection
        wrapper = ModelWrapperForPretraining(
            mode=Mode.training,
            pretrained_config=dict(
                model_type="gpt_dolomite", vocab_size=96, n_positions=32, n_embd=32, n_layer=2,
                n_head=4, num_key_value_heads=2, attention_head_type="gqa",
                position_embedding_type="rope", activation_function="swiglu",
                normalization_function="rmsnorm", add_bias=False, resid_pdrop=0.0,
                embd_pdrop=0.0, attn_pdrop=0.0,
            ),
            dtype="fp32",
            sequence_length=32,
            zero_stage=3,
            sequence_parallel=True,
            gradient_checkpointing_args={"checkpoint_every": 1},
        )
        params = wrapper.init_params(jax.random.PRNGKey(0), mesh_2x2x2)
        return jax.value_and_grad(lambda p: wrapper.loss(p, text, train=True)), params

    with mesh_2x2x2:
        fn, params = loss_and_grad()
        loss_ref, grads_ref = jax.jit(fn)(params)
        with kernel_overrides(rmsnorm="pallas", fused_rope_qkv="pallas"):
            fn, params = loss_and_grad()
            jaxpr = str(jax.make_jaxpr(fn)(params))
            loss_ker, grads_ker = jax.jit(fn)(params)
    # forward AND backward kernels sit inside shard_maps (2 norms + rope per block, ln_f)
    assert jaxpr.count("shard_map") >= 2 * (2 * 3 + 1)
    np.testing.assert_allclose(float(loss_ker), float(loss_ref), rtol=1e-6)
    for ker, ref in zip(jax.tree.leaves(grads_ker), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-6, rtol=1e-5)


def test_quantize_kernel_runs_per_shard_under_a_mesh(mesh_2x2x2):
    """The page-quantization kernel under a tp mesh: kv heads shard like the pool, and
    the bytes stay those of the XLA reference."""
    from flax import linen as nn

    from dolomite_engine_tpu.ops.kv_quant import quantize_pages_xla
    from dolomite_engine_tpu.ops.pallas.kv_quant import quantize_pages_pallas
    from dolomite_engine_tpu.parallel.sharding import get_logical_axis_rules

    values = jax.random.normal(jax.random.PRNGKey(0), (6, PAGE, 4, 8), jnp.float32)
    valid = jnp.arange(PAGE)[None, :] < jnp.asarray([[16], [3], [0], [9], [1], [16]])
    q_ref, s_ref = quantize_pages_xla(values, valid, 127.0, jnp.int8)
    with mesh_2x2x2, nn.logical_axis_rules(get_logical_axis_rules(stage=0)):
        jaxpr = str(jax.make_jaxpr(lambda v, m: quantize_pages_pallas(v, m, 127.0, jnp.int8))(values, valid))
        q_ker, s_ker = jax.jit(lambda v, m: quantize_pages_pallas(v, m, 127.0, jnp.int8))(values, valid)
    assert "shard_map" in jaxpr
    np.testing.assert_array_equal(np.asarray(q_ker), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s_ker), np.asarray(s_ref))
