"""The chunked head loss with per-token weights (`ops/loss.fused_linear_cross_entropy(weights=)`,
`fused_linear_token_cross_entropy`): values and every gradient against a plain ``jnp`` loss;
the gradient with respect to a weight is that token's own loss; unit weights are the weightless
loss; the Pallas `fused_ce` family takes the weights too; and the two rules' programs are held
apart by the sha256 of their jaxprs, forward and backward: the per-token rule's (what Ouro's gate
learns through) taken at PR 39's parent dd1a9eb and equal on both sides of it, the weightless
call's taken anew by PR 39 on purpose (its differentiated forward now forms the gradients).

Tolerances: float32 on both sides, another order of summation: 1e-6 relative on a loss, 1e-6
absolute on gradients of size ~0.1."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.ops.loss import IGNORE_INDEX, fused_linear_cross_entropy, fused_linear_token_cross_entropy
from dolomite_engine_tpu.ops.pallas import kernel_overrides

B, S, H, V = 3, 20, 16, 50  # S is no multiple of the chunk: the padded tail is cut off again


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(V, H)) * 0.3, jnp.float32)
    labels = rng.integers(0, V, size=(B, S))
    labels[0, 3:6] = IGNORE_INDEX
    weights = jnp.asarray(rng.uniform(size=(B, S)), jnp.float32)
    return hidden, table, jnp.asarray(labels), weights


def plain_terms(hidden, table, labels):
    logits = hidden @ table.T
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return lse - picked, lse


def plain_loss(hidden, table, labels, weights, z):
    token_loss, lse = plain_terms(hidden, table, labels)
    valid = labels != IGNORE_INDEX
    return jnp.sum(jnp.where(valid, weights * (token_loss + z * lse**2), 0.0)) / jnp.sum(valid)


def fused(labels, z, **kwargs):
    return lambda h, t, w: fused_linear_cross_entropy(
        h, t, labels, chunk_size=8, upcast=True, compute_dtype=jnp.float32, z_loss_coef=z, weights=w, **kwargs
    )


@pytest.mark.parametrize("z", [0.0, 1e-2], ids=["no_z_loss", "z_loss"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_weighted_loss_and_its_three_gradients_follow_the_plain_loss(operands, z, backend):
    hidden, table, labels, weights = operands
    with kernel_overrides(fused_ce=backend):
        loss, grads = jax.value_and_grad(fused(labels, z), argnums=(0, 1, 2))(hidden, table, weights)
    ref, ref_grads = jax.value_and_grad(lambda h, t, w: plain_loss(h, t, labels, w, z), argnums=(0, 1, 2))(hidden, table, weights)
    np.testing.assert_allclose(loss, ref, rtol=1e-6)
    for mine, theirs in zip(grads, ref_grads):
        np.testing.assert_allclose(mine, theirs, atol=1e-6)


def test_the_gradient_with_respect_to_a_weight_is_that_token_s_loss(operands):
    hidden, table, labels, weights = operands
    z = 1e-2
    d_weights = jax.grad(fused(labels, z), argnums=2)(hidden, table, weights)
    token_loss, lse = plain_terms(hidden, table, labels)
    valid = labels != IGNORE_INDEX
    expected = jnp.where(valid, token_loss + z * lse**2, 0.0) / jnp.sum(valid)
    np.testing.assert_allclose(d_weights, expected, atol=1e-6)
    assert float(jnp.abs(d_weights[0, 3:6]).max()) == 0.0  # no label, no loss, whatever the weight
    # ... and the hidden states' gradient of a token scales with its weight
    d_hidden = jax.grad(fused(labels, z), argnums=0)
    doubled = weights.at[1, 7].multiply(2.0)
    np.testing.assert_allclose(d_hidden(hidden, table, doubled)[1, 7], 2 * d_hidden(hidden, table, weights)[1, 7], rtol=1e-5, atol=1e-8)


def test_unit_weights_are_the_weightless_loss_and_the_token_terms_are_its_terms(operands):
    hidden, table, labels, weights = operands
    for z in (0.0, 1e-2):
        weightless = fused_linear_cross_entropy(hidden, table, labels, chunk_size=8, compute_dtype=jnp.float32, z_loss_coef=z)
        np.testing.assert_allclose(fused(labels, z)(hidden, table, jnp.ones_like(weights)), weightless, rtol=1e-6)
    token_loss, lse = fused_linear_token_cross_entropy(hidden, table, labels, chunk_size=8, compute_dtype=jnp.float32)
    assert token_loss.shape == lse.shape == (B, S) and token_loss.dtype == lse.dtype == jnp.float32
    ref_loss, ref_lse = plain_terms(hidden, table, labels)
    np.testing.assert_allclose(token_loss, jnp.where(labels != IGNORE_INDEX, ref_loss, 0.0), atol=1e-6)
    valid = labels != IGNORE_INDEX
    np.testing.assert_allclose(lse, jnp.where(valid, ref_lse, 0.0), rtol=1e-6)  # 0 where there is no label, as its gradient is
    # the log-sum-exp is differentiable too (the z-loss goes through it)
    g = jax.grad(lambda h: jnp.sum(fused_linear_token_cross_entropy(h, table, labels, chunk_size=8, compute_dtype=jnp.float32)[1] ** 2))(hidden)
    np.testing.assert_allclose(g, jax.grad(lambda h: jnp.sum(jnp.where(valid, plain_terms(h, table, labels)[1], 0.0) ** 2))(hidden), atol=1e-5)


def weightless_jaxpr(z, upcast) -> str:
    hidden, table, labels = jnp.zeros((2, 24, 16), jnp.bfloat16), jnp.zeros((40, 16), jnp.float32), jnp.zeros((2, 24), jnp.int32)
    loss = lambda h, t: fused_linear_cross_entropy(h, t, labels, chunk_size=8, upcast=upcast, compute_dtype=jnp.bfloat16, z_loss_coef=z)  # noqa: E731
    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(hidden, table))


@pytest.mark.parametrize(
    "z, upcast, sha256",
    [
        (0.0, True, "50c8a0acfc98768b3922d80d2a7e2ffbac722ec8dcfff7b97fdf6f3e9fe01bff"),
        (1e-4, False, "5be3a811acd1349eba5d45b71f43a0e65e58aaddfc94f6997c3503a7fa5edd95"),
    ],
    ids=["upcast", "z_loss_compute_dtype"],
)
def test_the_weightless_call_traces_to_the_program_it_traced_to_before(z, upcast, sha256):
    """Loss and both gradients of the weightless call, as a jaxpr's text, hashed on this
    installation's jax. **PR 39 replaced both hashes on purpose** (d345296's were 09d77a4a...
    and 04b3cf90...): the summed rule's differentiated forward keeps a token block's logits and
    forms the gradients there, its backward rule only scales them — the dense and expert
    cells' head is another program, one product shorter. A later PR that moves these moves
    five of the six cells' step."""
    text = weightless_jaxpr(z, upcast)
    assert hashlib.sha256(text.encode()).hexdigest() == sha256
    # ... and a weighted call is another program (the hash is not blind)
    labels = jnp.zeros((2, 24), jnp.int32)
    weighted = str(jax.make_jaxpr(lambda h, t, w: fused_linear_cross_entropy(h, t, labels, chunk_size=8, weights=w))(
        jnp.zeros((2, 24, 16), jnp.bfloat16), jnp.zeros((40, 16), jnp.float32), jnp.ones((2, 24))
    ))
    assert weighted != text


def per_token_jaxpr(upcast, logit_scale, through_weights) -> str:
    hidden, table, labels = jnp.zeros((2, 24, 16), jnp.bfloat16), jnp.zeros((40, 16), jnp.float32), jnp.zeros((2, 24), jnp.int32)

    def weighed(h, t, w):
        if through_weights:
            return fused_linear_cross_entropy(h, t, labels, chunk_size=8, compute_dtype=jnp.bfloat16, z_loss_coef=1e-4, weights=w)
        loss, lse = fused_linear_token_cross_entropy(h, t, labels, chunk_size=8, upcast=upcast, logit_scale=logit_scale, compute_dtype=jnp.bfloat16)
        return jnp.sum(w * (loss + 1e-4 * lse**2))

    return str(jax.make_jaxpr(jax.value_and_grad(weighed, argnums=(0, 1, 2)))(hidden, table, jnp.ones((2, 24), jnp.float32)))


@pytest.mark.parametrize(
    "upcast, logit_scale, through_weights, sha256",
    [
        (True, None, False, "8b2bf59289b137982835460edcacfd9a6b06e3c1002867c915e5d5b9fdf70100"),
        (False, 0.5, False, "59a2351871b50a4bccc3221d0e4ef314c576d99c6a8731397db6c07dab4151a0"),
        (True, None, True, "856394d40a095f123684472233c26ad73aa4ee011af4e9463c5749a511d53cd3"),
    ],
    ids=["token_terms_upcast", "token_terms_compute_dtype_logit_scale", "weights"],
)
def test_the_per_token_rule_traces_to_the_program_it_traced_to_before(upcast, logit_scale, through_weights, sha256):
    """The per-token rule — every token's terms handed out, a token's own cotangents taken,
    the logits recomputed a vocabulary tile at a time — with its three gradients, as a jaxpr's
    text hashed at PR 39's parent (dd1a9eb, this installation's jax): the looped cell's head is
    the program it was while the summed rule beside it changed."""
    assert hashlib.sha256(per_token_jaxpr(upcast, logit_scale, through_weights).encode()).hexdigest() == sha256
