"""The Mamba-2 ops (`ops/mamba2.py`): the chunked scan against the token-by-token recurrence,
forward and gradients, and the resets at document boundaries of a packed row — state and
convolution taps — against the documents run apart. The scan's Pallas kernels
(`ops/pallas/mamba2.py`, interpreted here) against the recurrence and the `jnp` form at the
published head layout, and the rule that chooses between the two lowerings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.ops import mamba2
from dolomite_engine_tpu.ops.mamba2 import (
    causal_conv1d,
    gated_group_rmsnorm,
    mamba2_chunked,
    mamba2_recurrent,
    mamba2_scan,
    scan_lowering,
)
from dolomite_engine_tpu.ops.pallas.mamba2 import kept_bytes, mamba2_chunked_kernel

B, T, H, P, G, N = 2, 64, 8, 4, 2, 8
DOCS = (10, 37, 17)  # three documents in a row of 64


def scan_inputs(seed=0, batch=B, length=T):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(k[0], (batch, length, H, P)),
        jax.nn.softplus(jax.random.normal(k[1], (batch, length, H))),
        -jnp.exp(jax.random.normal(k[2], (H,)) * 0.5),
        jax.random.normal(k[3], (batch, length, G, N)),
        jax.random.normal(k[4], (batch, length, G, N)),
        jax.random.normal(k[5], (H,)),
    )


def segments():
    return jnp.asarray(np.stack([np.repeat([1, 2, 3], DOCS), np.repeat([1, 2], [33, 31])]))


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_scan_is_the_recurrence(packed, chunk):
    args = scan_inputs()
    seg = segments() if packed else None
    chunked = mamba2_chunked(*args, seg, chunk_size=chunk)
    recurrent = mamba2_recurrent(*args, seg)
    np.testing.assert_allclose(chunked, recurrent, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("packed", [False, True])
def test_chunked_scan_gradients_are_the_recurrence_s(packed):
    args = scan_inputs(1)
    seg = segments() if packed else None
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * weight), argnums=tuple(range(6)))(*args)  # noqa: E731
    chunked = grad(lambda *a: mamba2_chunked(*a, seg, chunk_size=16))
    recurrent = grad(lambda *a: mamba2_recurrent(*a, seg))
    for mine, ref in zip(chunked, recurrent):
        np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-4 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("form", ["chunked", "recurrent"])
def test_a_packed_row_is_its_documents_run_apart(form):
    """No state crosses a boundary: also where one falls inside a chunk (10, 47) and where a
    document spans chunks."""
    x, dt, a, b, c, d = scan_inputs(2, batch=1)
    seg = segments()[:1]
    run = (lambda *v, s: mamba2_chunked(*v, s, chunk_size=16)) if form == "chunked" else (lambda *v, s: mamba2_recurrent(*v, s))
    packed = run(x, dt, a, b, c, d, s=seg)
    start = 0
    for length in DOCS:
        cut = lambda v: v[:, start : start + length]  # noqa: E731
        if form == "chunked":  # one chunk the length of the document
            alone = mamba2_chunked(cut(x), cut(dt), a, cut(b), cut(c), d, None, chunk_size=length)
        else:
            alone = mamba2_recurrent(cut(x), cut(dt), a, cut(b), cut(c), d, None)
        np.testing.assert_allclose(packed[:, start : start + length], alone, rtol=2e-5, atol=2e-5)
        start += length
    # and the boundary matters: without the segments the later documents read the earlier
    assert float(jnp.abs(run(x, dt, a, b, c, d, s=None) - packed)[:, DOCS[0] :].max()) > 1e-2


def test_causal_conv_is_the_sum_it_says_and_resets_at_boundaries():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, T, 6)).astype(np.float32)
    w = rng.normal(size=(6, 4)).astype(np.float32)
    bias = rng.normal(size=(6,)).astype(np.float32)
    seg = np.asarray(segments()[:1])
    expected = np.zeros_like(x)
    for t in range(T):
        for k in range(4):
            source = t - (3 - k)
            if source >= 0 and seg[0, source] == seg[0, t]:
                expected[0, t] += w[:, k] * x[0, source]
    expected += bias
    np.testing.assert_allclose(causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), jnp.asarray(seg)), expected, rtol=1e-5, atol=1e-5)
    # a document alone gives the same rows
    start = DOCS[0]
    alone = causal_conv1d(jnp.asarray(x[:, start : start + DOCS[1]]), jnp.asarray(w), jnp.asarray(bias))
    np.testing.assert_allclose(alone, expected[:, start : start + DOCS[1]], rtol=1e-5, atol=1e-5)


def test_chunk_must_divide_the_row():
    with pytest.raises(ValueError, match="multiple of the chunk"):
        mamba2_chunked(*scan_inputs(), None, chunk_size=48)


def test_gated_group_norm_normalises_each_group():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    gate = jax.random.normal(jax.random.PRNGKey(1), (3, 32))
    out = gated_group_rmsnorm(y, gate, jnp.full((32,), 2.0), groups=4, eps=0.0)
    h = np.asarray(y * jax.nn.silu(gate)).reshape(3, 4, 8)
    expected = 2.0 * h / np.sqrt(np.mean(h**2, axis=-1, keepdims=True))
    np.testing.assert_allclose(out, expected.reshape(3, 32), rtol=1e-5, atol=1e-6)


# ---- the kernels (`ops/pallas/mamba2.py`), interpreted: the published head layout (heads of 64
# in groups of 8, state 128, chunk 128) cut in heads and length only
KH, KP, KG, KN, KL, KT = 16, 64, 2, 128, 128, 512
# a boundary inside a chunk (70), one exactly at a chunk's edge (128), a document over three
# chunks (128 .. 428) that ends inside the last
KERNEL_DOCS = (70, 58, 300, 84)


def kernel_inputs(seed=0, batch=1, length=KT, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(k[0], (batch, length, KH, KP)).astype(dtype),
        jax.nn.softplus(jax.random.normal(k[1], (batch, length, KH)) - 1.0),
        -jnp.exp(jax.random.normal(k[2], (KH,)) * 0.5),
        (jax.random.normal(k[3], (batch, length, KG, KN)) * 0.3).astype(dtype),
        (jax.random.normal(k[4], (batch, length, KG, KN)) * 0.3).astype(dtype),
        jax.random.normal(k[5], (KH,)),
    )


def kernel_segments(batch=1):
    rows = [np.repeat([1, 2, 3, 4], KERNEL_DOCS), np.repeat([1, 2], [256, 256])]
    return jnp.asarray(np.stack(rows[:batch]))


@pytest.mark.parametrize("against", ["recurrent", "jnp"])
@pytest.mark.parametrize("packed", [False, True])
def test_scan_kernel_is_the_recurrence_and_the_jnp_form(packed, against):
    args = kernel_inputs(batch=2)
    seg = kernel_segments(2) if packed else None
    mine = mamba2_chunked_kernel(*args, seg, KL)
    ref = mamba2_recurrent(*args, seg) if against == "recurrent" else mamba2_chunked(*args, seg, chunk_size=KL)
    np.testing.assert_allclose(mine, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scan_kernel_gradients_are_the_jnp_form_s(dtype, packed):
    """The gradient of every input (x, dt, A, B, C, D). In float32 the two lowerings agree to
    rounding; in bfloat16 each is held to the `jnp` form's float32 gradient."""
    args32 = kernel_inputs(3)
    args = kernel_inputs(3, dtype=jnp.dtype(dtype))
    seg = kernel_segments() if packed else None
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def grad(f, operands):
        loss = lambda *a: jnp.sum(f(*a, seg, KL).astype(jnp.float32) * weight)  # noqa: E731
        return jax.grad(loss, argnums=tuple(range(6)))(*operands)

    truth = grad(mamba2_chunked, args32)
    mine = grad(mamba2_chunked_kernel, args)
    tolerance = 2e-5 if dtype == "float32" else 1e-2
    for got, ref in zip(mine, truth):
        assert got.dtype == ref.dtype or dtype == "bfloat16"
        gap = float(jnp.linalg.norm(got.astype(jnp.float32) - ref) / jnp.linalg.norm(ref))
        assert gap < tolerance, gap
    if dtype == "bfloat16":  # and no further from it than the `jnp` form in bfloat16 is
        theirs = grad(mamba2_chunked, args)
        for got, other, ref in zip(mine, theirs, truth):
            gap = lambda g: float(jnp.linalg.norm(g.astype(jnp.float32) - ref) / jnp.linalg.norm(ref))  # noqa: E731
            assert gap(got) < 1.5 * gap(other) + 1e-4


def test_scan_kernel_a_packed_row_is_its_documents_run_apart():
    """No state crosses a boundary inside a chunk, at a chunk's edge or after three chunks:
    every document of the packed row, run alone through the kernel (padded behind to whole
    chunks: the scan is causal), gives the row's outputs."""
    x, dt, a, b, c, d = kernel_inputs(2)
    seg = kernel_segments()
    packed = mamba2_chunked_kernel(x, dt, a, b, c, d, seg, KL)
    start = 0
    for length in KERNEL_DOCS:
        whole = -(-length // KL) * KL
        cut = lambda v: jnp.pad(v[:, start : start + length], ((0, 0), (0, whole - length)) + ((0, 0),) * (v.ndim - 2))  # noqa: E731
        alone = mamba2_chunked_kernel(cut(x), cut(dt), a, cut(b), cut(c), d, None, KL)
        np.testing.assert_allclose(packed[:, start : start + length], alone[:, :length], rtol=2e-5, atol=2e-5)
        start += length
    unreset = mamba2_chunked_kernel(x, dt, a, b, c, d, None, KL)
    assert float(jnp.abs(unreset - packed)[:, KERNEL_DOCS[0] :].max()) > 1e-2


def test_scan_kernel_refuses_what_it_does_not_tile():
    with pytest.raises(ValueError, match="does not tile"):
        mamba2_chunked_kernel(*scan_inputs(), None, 16)


def test_the_scan_s_lowering_is_chosen_by_backend_mesh_and_shape(monkeypatch):
    """What the choice observes (as `ops/moe._share_grouped_product`): the backend, whether
    the trace stands under a mesh of several devices, and whether the kernel tiles the
    shapes. Here, on the CPU, the `jnp` form it is."""
    import flax.linen as nn
    from jax.sharding import Mesh

    x_shape, bc_shape = (2, KT, KH, KP), (2, KT, KG, KN)
    assert scan_lowering(x_shape, bc_shape, KL)["form"] == "jnp"
    assert scan_lowering(x_shape, bc_shape, KL)["reason"] == "backend"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = scan_lowering(x_shape, bc_shape, KL, itemsize=2)
    assert (plan["form"], plan["chunk"], plan["launches_per_pass"]) == ("kernel", KL, 1)
    kept = kept_bytes(*x_shape, KG, KN, KL, 2)
    assert plan["kept_bytes"] == sum(kept.values()) and plan["kept_state_bytes"] == 2 * (KT // KL) * KN * KH * KP * 4
    assert jax.device_count() > 1  # tests/conftest.py asks for eight CPU devices
    with Mesh(np.asarray(jax.devices()), ("fsdp",)), nn.logical_axis_rules((("embed", "fsdp"),)):
        assert scan_lowering(x_shape, bc_shape, KL)["reason"] == "mesh"
    with Mesh(np.asarray(jax.devices()[:1]), ("fsdp",)), nn.logical_axis_rules((("embed", "fsdp"),)):
        assert scan_lowering(x_shape, bc_shape, KL)["form"] == "kernel"
    # a row the chunk does not divide is one chunk of the whole row, in `jnp`
    odd = scan_lowering((2, KT + 64, KH, KP), (2, KT + 64, KG, KN), KL)
    assert (odd["form"], odd["reason"], odd["chunk"]) == ("jnp", "shape", KT + 64)
    # and the tests' tiny heads, a chunk of 64, a state of 16 are not the kernel's
    assert scan_lowering((B, T, H, P), (B, T, G, N), 16)["reason"] == "shape"
    assert scan_lowering(x_shape, bc_shape, 64)["reason"] == "shape"
    assert scan_lowering(x_shape, (2, KT, KG, 16), KL)["reason"] == "shape"


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_the_entry_runs_the_lowering_it_chose(form, monkeypatch):
    """`mamba2_scan` on the CPU is the `jnp` form; told it stands on a TPU it is the kernel
    (interpreted), value and gradient; either way it reports its choice to a watcher."""
    from dolomite_engine_tpu.utils import packages

    args = kernel_inputs(4)
    seg = kernel_segments()
    if form == "kernel":
        monkeypatch.setattr(packages, "pallas_interpret_mode", lambda: True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    loss = lambda f: (lambda *a: jnp.sum(f(*a, seg, KL) ** 2))  # noqa: E731
    with mamba2.watch_scan_lowerings() as seen:
        value, grads = jax.value_and_grad(loss(mamba2_scan), argnums=(0, 1, 3))(*args)
        assert ("pallas_call" in str(jax.make_jaxpr(loss(mamba2_scan))(*args))) == (form == "kernel")
    assert [plan["form"] for plan in seen] == [form, form]
    ref_value, ref_grads = jax.value_and_grad(loss(mamba2_chunked), argnums=(0, 1, 3))(*args)
    np.testing.assert_allclose(value, ref_value, rtol=1e-5)
    for mine, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(mine, ref, rtol=1e-3, atol=1e-5 * float(jnp.abs(ref).max()))
