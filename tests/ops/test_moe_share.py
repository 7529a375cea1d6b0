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


# ------------------------------------------- the row movements follow `routed`, not `capacity`

HELD, FIRST, TOP_K, CAPACITY, BLOCK_ROWS = 4, 8, 2, 16, 4  # 16 tokens x 2 slots: two chunks of 16


def swiglu(h):
    from dolomite_engine_tpu.ops.activations import get_activation_function

    return get_activation_function("swiglu")(h)


def routed_layer(routed: int, act):
    """A layer of 16 tokens whose router sends exactly `routed` of the 32 token-slots, spread
    over the tokens, to the four held experts."""
    rng = np.random.default_rng(routed)
    tokens, slots = 16, 16 * TOP_K
    chosen = rng.integers(0, FIRST, size=slots)  # absent experts, below the held ones ...
    chosen[rng.integers(0, 2, size=slots) == 1] += FIRST + HELD  # ... and above them
    here = rng.permutation(slots)[:routed]
    chosen[here] = FIRST + rng.integers(0, HELD, size=routed)
    k = jax.random.split(jax.random.PRNGKey(routed), 4)
    up = 2 * F if act is swiglu else F
    return dict(
        x=jax.random.normal(k[0], (tokens, D)),
        weights=jax.random.uniform(k[1], (tokens, TOP_K), minval=0.1),
        w_fc=jax.random.normal(k[2], (HELD, D, up)) * 0.3,
        w_proj=jax.random.normal(k[3], (HELD, F, D)) * 0.3,
    ), jnp.asarray(chosen.reshape(tokens, TOP_K), jnp.int32)


def masked_take_and_scatter_add(x, weights, w_fc, w_proj, chosen, act):
    """The layer in plain `jnp`, every slot at once: sort, a masked take, each row through its
    expert's two matrices, a masked and weighted scatter-add."""
    local = chosen.reshape(-1) - FIRST
    key = jnp.where((local >= 0) & (local < HELD), local, HELD)
    order = jnp.argsort(key, stable=True)
    valid = jnp.arange(key.shape[0]) < jnp.sum(key < HELD)
    token, expert = order // TOP_K, jnp.minimum(jnp.take(key, order), HELD - 1)
    xs = jnp.where(valid[:, None], jnp.take(x, token, axis=0), 0)
    h = act(jnp.einsum("sd,sdf->sf", xs, jnp.take(w_fc, expert, axis=0)))
    y = jnp.einsum("sf,sfd->sd", h, jnp.take(w_proj, expert, axis=0))
    scale = jnp.where(valid, jnp.take(weights.reshape(-1), order), 0.0)
    return jnp.zeros_like(x).at[token].add(jnp.where(valid[:, None], y, 0) * scale[:, None])


def value_and_grads(layer_fn, operands, chosen, act):
    def loss(x, weights, w_fc, w_proj):
        return jnp.sum(jnp.sin(layer_fn(x, weights, w_fc, w_proj, chosen, act)))

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*operands.values())


def held_share(x, weights, w_fc, w_proj, chosen, act):
    return experts_held_ragged(x, weights, chosen, w_fc, w_proj, act, 16, FIRST, capacity=CAPACITY)[0]


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of `BLOCK_ROWS` rows, as the shapes of a toy would never give."""
    monkeypatch.setattr(moe, "_WALK_BLOCK_BYTES", BLOCK_ROWS * D * 4)
    assert moe._walk_block_rows(CAPACITY, D, 4) == BLOCK_ROWS


@pytest.mark.parametrize("act", [relu2, swiglu], ids=["relu2", "gated"])
@pytest.mark.parametrize(
    "routed", [0, 1, BLOCK_ROWS - 1, BLOCK_ROWS, BLOCK_ROWS + 1, CAPACITY, CAPACITY + BLOCK_ROWS + 1]
)  # the last: two chunks, the second part-filled
def test_row_movements_that_stop_at_the_last_routed_row_give_the_masked_take_and_scatter_add(
    small_blocks, routed, act
):
    operands, chosen = routed_layer(routed, act)
    counters = experts_held_ragged(
        operands["x"], operands["weights"], chosen, operands["w_fc"], operands["w_proj"], act, 16, FIRST, capacity=CAPACITY
    )[1]
    assert int(counters["routed_slots"]) == routed
    mine = value_and_grads(held_share, operands, chosen, act)
    reference = value_and_grads(masked_take_and_scatter_add, operands, chosen, act)
    np.testing.assert_allclose(mine[0], reference[0], rtol=1e-5)
    for name, got, want in zip(operands, mine[1], reference[1]):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6, err_msg=name)
    # and the same under the transformations the models put round the layer
    under = jax.jit(jax.checkpoint(lambda *a: held_share(*a, chosen, act)))
    np.testing.assert_allclose(
        jax.grad(lambda *a: jnp.sum(jnp.sin(under(*a))))(*operands.values()), mine[1][0], rtol=1e-5, atol=1e-7
    )


# ------------------------------------- the activation and the group sizes follow `routed` too

FORMS = ["xla_loop", "pallas"]  # the kernel runs interpreted here


@pytest.fixture
def activation_in(monkeypatch):
    """The activation's walk in a form and a block that the shapes of a toy would never give."""

    def force(form):
        monkeypatch.setattr(
            moe, "_share_activation", lambda rows, act, capacity, width: moe._Activation(act, BLOCK_ROWS, form)
        )

    return force


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("act", [relu2, swiglu], ids=["relu2", "gated"])
@pytest.mark.parametrize("count", [0, 1, BLOCK_ROWS - 1, BLOCK_ROWS, BLOCK_ROWS + 1, CAPACITY])
def test_the_walked_activation_is_the_activation_on_the_rows_below_count(count, act, form):
    """Values and gradients, whatever lies past `count` in the product and in the cotangent."""
    k = jax.random.split(jax.random.PRNGKey(count), 2)
    below = (jnp.arange(CAPACITY) < count)[:, None]
    h = jax.random.normal(k[0], (CAPACITY, 2 * F))
    d_out = jax.random.normal(k[1], jax.eval_shape(act, h).shape)
    plan = moe._Activation(act, BLOCK_ROWS, form)
    out, pull = jax.vjp(lambda h: moe._activate_rows(plan, h, jnp.int32(count)), jnp.where(below, h, jnp.nan))
    (d_h,) = pull(jnp.where(below, d_out, jnp.nan))
    want, want_pull = jax.vjp(act, h)
    assert out.shape == want.shape and d_h.shape == h.shape
    np.testing.assert_allclose(out[:count], want[:count], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(d_h[:count], want_pull(d_out)[0][:count], rtol=1e-6, atol=1e-7)
    # and no block after the one that holds the last routed row was touched
    blocks = -(-count // BLOCK_ROWS)
    if form == "xla_loop":
        assert not np.asarray(out[blocks * BLOCK_ROWS :]).any() and not np.asarray(d_h[blocks * BLOCK_ROWS :]).any()


def test_the_activation_walks_the_blocks_that_hold_routed_rows_and_no_more():
    """The loop's trip count is ``ceil(count / block_rows)``: a full `capacity` walks every
    block once, which is all the walk can cost over the plain pass (its loop steps)."""
    calls = []

    def counting(h):
        jax.debug.callback(lambda: calls.append(1))
        return relu2(h)

    plan = moe._Activation(counting, BLOCK_ROWS, "xla_loop")
    for count in (0, BLOCK_ROWS + 1, CAPACITY):
        calls.clear()
        jax.block_until_ready(moe._activate_rows(plan, jnp.ones((CAPACITY, F)), jnp.int32(count)))
        jax.effects_barrier()
        assert len(calls) == -(-count // BLOCK_ROWS)


def test_the_activation_kernel_is_for_one_tpu_and_widths_that_fill_lane_rows(monkeypatch):
    """What the choice observes: where a kernel can be launched (`_one_tpu`), both widths and
    the block's rows. The cells' shapes: lfm2 and joyai take the kernel, the tower's 1856 the loop."""
    x = jnp.zeros((8, D), jnp.bfloat16)
    plan = moe._share_activation(x, swiglu, 65536, 3072)
    assert (plan.form, plan.block_rows) == ("xla_loop", 1024)  # no TPU here: 8 MiB of 3072 bfloat16
    monkeypatch.setattr(moe, "_one_tpu", lambda rows: True)
    assert moe._share_activation(x, swiglu, 65536, 3072)[1:] == (512, "pallas")  # lfm2
    assert moe._share_activation(x, swiglu, 32768, 1536)[1:] == (1024, "pallas")  # joyai
    assert moe._share_activation(x, relu2, 24576, 1856)[1:] == (2048, "xla_loop")  # the tower
    assert moe._share_activation(x, swiglu, 24, 256)[1:] == (24, "xla_loop")  # no whole sublane tiles


@pytest.mark.parametrize("act", [relu2, swiglu], ids=["relu2", "gated"])
@pytest.mark.parametrize("routed", [BLOCK_ROWS + 1, CAPACITY + BLOCK_ROWS + 1], ids=["at_once", "in_chunks"])
def test_the_layer_with_the_activation_kernel_gives_the_masked_take_and_scatter_add(
    small_blocks, activation_in, routed, act
):
    activation_in("pallas")
    operands, chosen = routed_layer(routed, act)
    assert "pallas_call" in str(jax.make_jaxpr(lambda *a: held_share(*a, chosen, act))(*operands.values()))
    mine = value_and_grads(held_share, operands, chosen, act)
    reference = value_and_grads(masked_take_and_scatter_add, operands, chosen, act)
    np.testing.assert_allclose(mine[0], reference[0], rtol=1e-5)
    for name, got, want in zip(operands, mine[1], reference[1]):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6, err_msg=name)


def chosen_slots(case: str) -> np.ndarray:
    """32 token-slots over 16 experts, of which `FIRST .. FIRST + HELD - 1` are held."""
    rng = np.random.default_rng(7)
    slots = 16 * TOP_K
    absent = np.where(rng.integers(0, 2, size=slots) == 1, rng.integers(0, FIRST, size=slots), rng.integers(FIRST + HELD, 16, size=slots))
    return {
        "no_slot_held": rng.integers(0, FIRST, size=slots),  # all below the held experts
        "absent_slots_only": absent,  # below and above them
        "every_slot_held": FIRST + rng.integers(0, HELD, size=slots),
        "one_expert_only": np.full(slots, FIRST + 2),
        "a_random_draw": rng.integers(0, 16, size=slots),
    }[case]


@pytest.mark.parametrize("case", ["no_slot_held", "absent_slots_only", "every_slot_held", "one_expert_only", "a_random_draw"])
def test_the_group_sizes_read_off_the_sorted_keys_are_the_bincounts(case):
    operands, _ = routed_layer(5, relu2)
    chosen = chosen_slots(case)
    counters = experts_held_ragged(
        operands["x"], operands["weights"], jnp.asarray(chosen.reshape(16, TOP_K), jnp.int32),
        operands["w_fc"], operands["w_proj"], relu2, 16, FIRST, capacity=CAPACITY,
    )[1]
    want = np.bincount(chosen, minlength=16)[FIRST : FIRST + HELD]
    np.testing.assert_array_equal(counters["held_expert_rows"], want)
    assert counters["held_expert_rows"].dtype == jnp.int32
    assert int(counters["routed_slots"]) == want.sum() and int(counters["absent_slots"]) == 32 - want.sum()
    assert int(counters["fullest_expert_rows"]) == want.max()


@jax.custom_vjp
def poisoned_product(rows, bank, group_sizes):
    """`lax.ragged_dot` as a kernel that walks its groups' tiles leaves it: the rows of no
    group are not a number, in the product and in the rows' gradient, and the bank's gradient
    reads the groups' rows alone."""
    inside = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
    return jnp.where(inside, jax.lax.ragged_dot(rows, bank, group_sizes), jnp.nan)


def _poisoned_fwd(rows, bank, group_sizes):
    return poisoned_product(rows, bank, group_sizes), (rows, bank, group_sizes)


def _poisoned_bwd(kept, d_out):
    rows, bank, group_sizes = kept
    inside = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
    _, transpose = jax.vjp(lambda r, b: jax.lax.ragged_dot(r, b, group_sizes), jnp.where(inside, rows, 0), bank)
    d_rows, d_bank = transpose(jnp.where(inside, d_out, 0))
    return jnp.where(inside, d_rows, jnp.nan), d_bank, None


poisoned_product.defvjp(_poisoned_fwd, _poisoned_bwd)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("routed", [BLOCK_ROWS + 1, CAPACITY + BLOCK_ROWS + 1], ids=["at_once", "in_chunks"])
def test_what_the_products_leave_past_the_routed_rows_reaches_nothing(small_blocks, activation_in, monkeypatch, routed, form):
    """The first product's rows past `routed` are not a number, so the activation's are not
    either, in either form: neither reaches the layer's output nor any gradient."""
    activation_in(form)
    operands, chosen = routed_layer(routed, swiglu)
    reference = value_and_grads(masked_take_and_scatter_add, operands, chosen, swiglu)
    monkeypatch.setattr(moe, "_share_grouped_product", lambda rows: poisoned_product)
    # the poison is there: the buffer of a product holds it
    product = poisoned_product(jnp.ones((CAPACITY, D)), operands["w_fc"], jnp.asarray([1, 0, 2, 0], jnp.int32))
    assert np.isnan(np.asarray(product)[3:]).all() and np.isfinite(np.asarray(product)[:3]).all()
    mine = value_and_grads(held_share, operands, chosen, swiglu)
    np.testing.assert_allclose(mine[0], reference[0], rtol=1e-5)
    for name, got, want in zip(operands, mine[1], reference[1]):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6, err_msg=name)


def test_the_models_say_their_dispatch_plan_once(tmp_path):
    """`say_dispatch_plan` round two layers of one shape: one ``moe_dispatch_plan`` event, however
    often the model is traced, with what `experts_held_ragged` planned from the shapes."""
    import json

    from dolomite_engine_tpu.models.shared_expert_moe import say_dispatch_plan
    from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

    operands, chosen = routed_layer(5, relu2)
    telemetry = Telemetry(sink_path=str(tmp_path / "sink.jsonl"), rank=0)
    install_telemetry(telemetry)
    try:
        for _ in range(2):  # a model is traced more than once
            with say_dispatch_plan():
                for _ in range(2):
                    jax.eval_shape(lambda: held_share(*operands.values(), chosen, relu2))
    finally:
        uninstall_telemetry()
        telemetry.close()
    events = [json.loads(line) for line in open(tmp_path / "sink.jsonl")]
    plans = [e for e in events if e["kind"] == "event" and e["event"] == "moe_dispatch_plan"]
    assert len(plans) == 1, plans
    want = {
        "layers": 2, "capacity": CAPACITY, "block_rows": CAPACITY, "blocks_per_capacity": 1, "form": "xla_loop",
        "activation_block_rows": CAPACITY, "activation_form": "xla_loop", "group_sizes": "sorted_keys",
    }
    assert {k: plans[0][k] for k in want} == want
