"""The `nemotron_h` routing rule and the expert layer that is told which experts it holds
(`ops/moe.py`): `route_sigmoid_bias` against a hand-written top-k, and the shares of a layer
adding up to the layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.ops import moe
from dolomite_engine_tpu.ops.moe import experts_held_ragged, route, route_sigmoid_bias

T, D, F, E, K = 64, 16, 8, 32, 6


def relu2(h):
    return jnp.square(jax.nn.relu(h))


@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    return dict(
        x=jax.random.normal(k[0], (T, D)),
        router=jax.random.normal(k[1], (D, E)),
        bias=jax.random.normal(k[2], (E,)) * 0.3,
        w_fc=jax.random.normal(k[3], (E, D, F)) * 0.3,
        w_proj=jax.random.normal(k[4], (E, F, D)) * 0.3,
    )


def test_sigmoid_rule_against_a_hand_written_top_k(layer):
    logits = np.asarray(layer["x"] @ layer["router"], np.float64)
    bias = np.asarray(layer["bias"], np.float64)
    weights, chosen = route_sigmoid_bias(jnp.asarray(logits, jnp.float32), K, layer["bias"], 2.5)
    scores = 1.0 / (1.0 + np.exp(-logits))
    for t in range(T):
        by_hand = np.argsort(-(scores[t] + bias))[:K]  # chosen WITH the bias
        assert sorted(by_hand) == sorted(np.asarray(chosen[t]))
        picked = scores[t, np.asarray(chosen[t])]  # weighed WITHOUT it
        np.testing.assert_allclose(weights[t], 2.5 * picked / (picked.sum() + 1e-20), rtol=1e-5)
    # the bias chooses: without it other experts are taken somewhere
    _, unbiased = route_sigmoid_bias(jnp.asarray(logits, jnp.float32), K, jnp.zeros((E,)), 2.5)
    assert (np.sort(np.asarray(unbiased)) != np.sort(np.asarray(chosen))).any()
    # and it takes no gradient
    grad = jax.grad(lambda b: jnp.sum(route_sigmoid_bias(jnp.asarray(logits, jnp.float32), K, b, 2.5)[0] ** 2))(layer["bias"])
    assert not np.asarray(grad).any()


def test_the_first_rule_is_unchanged(layer):
    logits = layer["x"] @ layer["router"]
    weights, chosen = route(logits, 2)
    top = np.sort(np.asarray(logits), axis=-1)[:, -2:][:, ::-1]
    np.testing.assert_allclose(weights, jax.nn.softmax(jnp.asarray(top), axis=-1), rtol=1e-6)
    assert chosen.shape == (T, 2)


def uncut(layer):
    weights, chosen = route_sigmoid_bias(layer["x"] @ layer["router"], K, layer["bias"], 2.5)
    combine = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(chosen, E))
    h = relu2(jnp.einsum("td,edf->etf", layer["x"], layer["w_fc"]))
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", h, layer["w_proj"]), combine)


def shares(layer, held, capacity):
    weights, chosen = route_sigmoid_bias(layer["x"] @ layer["router"], K, layer["bias"], 2.5)
    parts = [
        experts_held_ragged(
            layer["x"], weights, chosen, layer["w_fc"][first : first + held], layer["w_proj"][first : first + held],
            relu2, E, first, capacity=capacity,
        )
        for first in range(0, E, held)
    ]
    return sum(p[0] for p in parts), [p[1] for p in parts]


@pytest.mark.parametrize("capacity", [None, 8, 100000])  # the default; overflowing: many chunks; all rows at once
def test_the_shares_of_a_layer_add_up_to_it(layer, capacity):
    total, counters = shares(layer, 8, capacity)
    np.testing.assert_allclose(total, uncut(layer), rtol=1e-4, atol=1e-5)
    assert sum(int(c["routed_slots"]) for c in counters) == T * K  # every slot is held somewhere, once
    for c in counters:
        assert int(c["routed_slots"]) + int(c["absent_slots"]) == T * K
        assert int(c["fullest_expert_rows"]) == int(jnp.max(c["held_expert_rows"]))
        assert int(jnp.sum(c["held_expert_rows"])) == int(c["routed_slots"])


def test_gradients_of_the_shares_add_up_too(layer):
    def via_shares(x, w_fc, w_proj):
        return jnp.sum(shares(dict(layer, x=x, w_fc=w_fc, w_proj=w_proj), 8, 8)[0] ** 2)

    def via_uncut(x, w_fc, w_proj):
        return jnp.sum(uncut(dict(layer, x=x, w_fc=w_fc, w_proj=w_proj)) ** 2)

    args = (layer["x"], layer["w_fc"], layer["w_proj"])
    for mine, ref in zip(jax.grad(via_shares, argnums=(0, 1, 2))(*args), jax.grad(via_uncut, argnums=(0, 1, 2))(*args)):
        np.testing.assert_allclose(mine, ref, rtol=1e-3, atol=1e-4 * float(jnp.abs(ref).max()))


def test_the_megablox_grouped_product_is_the_xla_one(layer, monkeypatch):
    """On one TPU the grouped products are jax's megablox kernel instead of `lax.ragged_dot`
    (here interpreted, the choice forced), forward and backward, in both the usual path and
    the chunked one; on the CPU, and under a mesh of several devices, `ragged_dot` it is."""
    def loss(x, w_fc, w_proj, capacity):
        return jnp.sum(shares(dict(layer, x=x, w_fc=w_fc, w_proj=w_proj), 8, capacity)[0] ** 2)

    from dolomite_engine_tpu.ops.pallas.moe import held_grouped_product

    args = (layer["x"], layer["w_fc"], layer["w_proj"])
    assert moe._share_grouped_product(layer["x"]) is jax.lax.ragged_dot  # no TPU here
    for capacity in (None, 16):
        reference = jax.value_and_grad(loss, argnums=(0, 1, 2))(*args, capacity)
        with monkeypatch.context() as patch:
            patch.setattr(moe, "_share_grouped_product", lambda rows: held_grouped_product)
            kernel = jax.value_and_grad(loss, argnums=(0, 1, 2))(*args, capacity)
            assert "pallas_call" in str(jax.make_jaxpr(lambda *a: loss(*a, capacity))(*args))
        np.testing.assert_allclose(kernel[0], reference[0], rtol=1e-5)
        for mine, ref in zip(kernel[1], reference[1]):
            np.testing.assert_allclose(mine, ref, rtol=1e-3, atol=1e-5 * float(jnp.abs(ref).max()))


def test_megablox_is_for_one_tpu_only(layer, monkeypatch):
    """What the choice observes: the backend, and whether the trace stands under a mesh of
    several devices (where the Mosaic kernel would need a `shard_map` that is not built)."""
    import flax.linen as nn
    from jax.sharding import Mesh

    from dolomite_engine_tpu.ops.pallas.moe import held_grouped_product

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._share_grouped_product(layer["x"]) is held_grouped_product
    assert jax.device_count() > 1  # tests/conftest.py asks for eight CPU devices
    with Mesh(np.asarray(jax.devices()), ("fsdp",)), nn.logical_axis_rules((("embed", "fsdp"),)):
        assert moe._share_grouped_product(layer["x"]) is jax.lax.ragged_dot
    with Mesh(np.asarray(jax.devices()[:1]), ("fsdp",)), nn.logical_axis_rules((("embed", "fsdp"),)):
        assert moe._share_grouped_product(layer["x"]) is held_grouped_product


def test_a_share_that_gets_every_slot_drops_none(layer):
    """All tokens alike: every one of them picks the same experts, so one share gets T x k
    rows — sixteen times the even split — and still computes them all."""
    same = dict(layer, x=jnp.broadcast_to(layer["x"][:1], (T, D)))
    total, counters = shares(same, 8, None)
    np.testing.assert_allclose(total, uncut(same), rtol=1e-4, atol=1e-5)
    assert max(int(c["fullest_expert_rows"]) for c in counters) == T
