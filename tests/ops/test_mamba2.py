"""The Mamba-2 ops (`ops/mamba2.py`): the chunked scan against the token-by-token recurrence,
forward and gradients, and the resets at document boundaries of a packed row — state and
convolution taps — against the documents run apart."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.ops.mamba2 import (
    causal_conv1d,
    gated_group_rmsnorm,
    mamba2_chunked,
    mamba2_recurrent,
)

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
