"""`joyai_llm_flash` (models/joyai_flash.py) at a small size on the CPU, against the plain
reference (`benchmark/reference/joyai_flash.py`) on seeded weights. The family's contract —
registered, logits of packed rows, both parts of the loss and every leaf's gradient, three AdamW
steps through the trainer's own step, the shares of an expert layer adding up to the reference's
uncut layer, what the family refuses, the lowered step — is `family_contract.py`'s; here is what is
the family's own: what its tree holds; accumulated micro-batches; the de-interleaved rotation
against the literal one; scores over a wider head than the values through `sdpa` and through the
splash kernel (interpreted); what multi-token prediction masks and adds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai_flash as reference
from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.joyai_flash import second_token_labels
from dolomite_engine_tpu.ops.loss import IGNORE_INDEX
from dolomite_engine_tpu.train_utils import make_train_step

from .family_contract import FAMILIES, batches, built, contract_tests, packed_row, program_tree, wrapper_for

CFG = FAMILIES["joyai_llm_flash"].cfg
globals().update(contract_tests("joyai_llm_flash"))


def test_the_tree_holds_the_multi_token_prediction_module_as_the_layer_after_the_last():
    names = FAMILIES["joyai_llm_flash"].W.leaves_by_name(built("joyai_llm_flash")[2])
    assert "layer3.mtp_eh_proj" in names and "layer0.mlp_c_fc" in names and "mtp" in program_tree("joyai_llm_flash")["transformer"]
    config = config_from_dict(CFG)
    assert config.head_dim == 12 and config.moe_shared_expert_intermediate_size == 12 and config.expert_layers == 3
    assert config.layout_record()["blocks_experts"] == 2 and config.layout_record()["experts_held"] == 8


def test_accumulated_micro_batches_add_their_counts_and_average_the_loss_s_parts():
    _, _, params, _ = built("joyai_llm_flash")
    wrapper = wrapper_for(CFG)
    import optax

    from dolomite_engine_tpu.distributed import TrainState

    optimizer = optax.sgd(0.0)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params), fp8=None)
    loss_fn = lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True)  # noqa: E731
    data = jnp.asarray(np.stack([b[:1] for b in batches(steps=2)]))  # [2 micro-batches, 1 row, T + 1]
    _, both = jax.jit(make_train_step(loss_fn, optimizer, gradient_accumulation_steps=2, has_aux=True))(state, {"text": data}, jax.random.PRNGKey(0))
    singles = [jax.jit(make_train_step(loss_fn, optimizer, has_aux=True))(state, {"text": data[i : i + 1]}, jax.random.PRNGKey(0))[1] for i in (0, 1)]
    for name in ("main_loss", "mtp_loss"):
        np.testing.assert_allclose(both["counters"][name], np.mean([s["counters"][name] for s in singles]), rtol=1e-6)
    for name in ("mtp_targets", "routed_slots", "held_expert_rows"):
        np.testing.assert_array_equal(both["counters"][name], sum(s["counters"][name] for s in singles))


# ---- latent attention's pieces

def test_deinterleaved_rotate_half_scores_are_the_literal_interleaved_rotation_s():
    from dolomite_engine_tpu.ops.rope import RoPEParams, apply_rotary_pos_emb, deinterleave_pairs, get_cos_sin

    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 64))
    positions = jnp.asarray(np.r_[np.arange(25), np.arange(15)])
    np.testing.assert_array_equal(deinterleave_pairs(jnp.arange(6.0)), [0, 2, 4, 1, 3, 5])
    cos_sin = get_cos_sin(RoPEParams.from_config(64, 32e6), positions[None])
    mine = [apply_rotary_pos_emb(deinterleave_pairs(x)[None], *cos_sin)[0] for x in (q, k)]
    literal = [reference.rotate_pairs(x, positions, 32e6) for x in (q, k)]
    # by hand, one pair: columns (2, 3) of head 1 at position 7 turn by 7 / theta^(2/64)
    angle = 7 / 32e6 ** (2 / 64)
    x0, x1 = float(q[7, 1, 2]), float(q[7, 1, 3])
    np.testing.assert_allclose(literal[0][7, 1, 2:4], [x0 * np.cos(angle) - x1 * np.sin(angle), x0 * np.sin(angle) + x1 * np.cos(angle)], rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        scores = lambda pair: jnp.einsum("qhd,khd->hqk", *pair)  # noqa: E731
        np.testing.assert_allclose(scores(mine), scores(literal), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(mine[0], deinterleave_pairs(literal[0]), rtol=1e-5, atol=1e-5)  # the same numbers, in another order


@pytest.mark.parametrize("path", ["sdpa", "splash_interpreted"])
def test_scores_over_192_with_values_of_128(path, monkeypatch):
    """The published head: scores over 192 columns, values of 128, packed documents — through the
    XLA path and through the splash kernel (interpreted), against the reference's attention; and
    the kernel counts the value width for the output it tags for the remat policy."""
    from dolomite_engine_tpu.enums import AttentionImplementation
    from dolomite_engine_tpu.ops import attention as ops

    seq, heads = 256, 2
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 1, seq, heads, 192)) * 0.3
    v = jax.random.normal(jax.random.PRNGKey(1), (1, seq, heads, 128))
    segments = jnp.asarray(np.repeat([1, 2, 3], [100, 28, 128]))[None]
    with jax.default_matmul_precision("highest"):
        ref = reference.attention(q[0], k[0], v[0], segments[0])
        if path == "sdpa":
            out = ops.attention(q, k, v, AttentionImplementation.sdpa, segment_ids=segments)
        else:
            with ops.watch_kernel_residuals() as seen:
                out = ops._splash_attention_local(q, k, v, segments, 192**-0.5, interpret=True)
            assert seen == [heads * seq * (128 * 4 + 4)]
    assert out.shape == (1, seq, heads, 128)
    np.testing.assert_allclose(out[0], ref, rtol=2e-4, atol=2e-5)


# ---- multi-token prediction

def test_second_token_labels_leave_out_what_crosses_a_document():
    #           doc 1: 5 7 0 | doc 2: 9 4 6 0 | doc 3: 8 ...
    text = jnp.asarray([[5, 7, 0, 9, 4, 6, 0, 8, 3]])
    wrapper = wrapper_for(CFG)
    batch = wrapper.prepare_inputs_and_labels(text)
    X = IGNORE_INDEX
    np.testing.assert_array_equal(batch["labels"], [[7, 0, X, 4, 6, 0, X, 3]])
    np.testing.assert_array_equal(second_token_labels(batch["labels"], batch["segment_ids"]), [[0, X, X, 6, 0, X, X, X]])
    np.testing.assert_array_equal(second_token_labels(batch["labels"], None), [[0, X, 4, 6, 0, X, 3, X]])
    main, second = reference.label_masks({"eos": 0}, text[0])
    np.testing.assert_array_equal(second, [True, False, False, True, True, False, False, False])
    np.testing.assert_array_equal(main, batch["labels"][0] != X)


def test_mtp_targets_across_a_boundary_carry_no_loss_and_a_zero_coefficient_leaves_the_main_loss_and_gradient():
    _, _, params, _ = built("joyai_llm_flash")
    text = jnp.asarray(packed_row((23, 41)))
    with_mtp, without = wrapper_for(CFG), wrapper_for(dict(CFG, mtp_loss_coef=0.0))
    grad = lambda w, t: jax.value_and_grad(lambda p: w.loss(p, t[None], train=True), has_aux=True)(params)  # noqa: E731
    (loss, counters), grads = grad(with_mtp, text)
    (loss0, counters0), grads0 = grad(without, text)
    assert float(loss0) == float(counters0["main_loss"]) == float(counters["main_loss"])
    np.testing.assert_allclose(loss, counters["main_loss"] + 0.3 * counters["mtp_loss"], rtol=1e-6)
    # with the coefficient at zero nothing of the MTP module has a gradient, and the blocks' is the main loss's alone
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(grads0["transformer"]["mtp"]))
    main_only = jax.grad(lambda p: with_mtp.model.apply({"params": p}, **with_mtp.prepare_inputs_and_labels(text[None])).counters["main_loss"])(params)
    for a, b in zip(jax.tree.leaves(grads0), jax.tree.leaves(main_only)):
        np.testing.assert_array_equal(a, b)
    # the head's table gets both passes' gradients: main's + 0.3 x the second pass's
    second_only = jax.grad(lambda p: with_mtp.model.apply({"params": p}, **with_mtp.prepare_inputs_and_labels(text[None])).counters["mtp_loss"])(params)
    np.testing.assert_allclose(
        grads["lm_head"]["kernel"], main_only["lm_head"]["kernel"] + 0.3 * second_only["lm_head"]["kernel"], rtol=1e-5, atol=1e-8
    )
    assert float(jnp.abs(second_only["lm_head"]["kernel"]).max()) > 0 and float(jnp.abs(second_only["transformer"]["wte"]["embedding"]).max()) > 0
    # a token whose change only reaches MTP targets across a boundary moves no MTP loss: the token after
    # the first document's eos is t_{i+1} of the eos position and t_{i+2} of the one before, both masked there
    moved = text.at[23].set((text[23] + 1) % 255 + 1)
    mtp_of = lambda t: with_mtp.model.apply({"params": params}, **with_mtp.prepare_inputs_and_labels(t[None]))  # noqa: E731
    labels = with_mtp.prepare_inputs_and_labels(text[None])
    second = second_token_labels(labels["labels"], labels["segment_ids"])[0]
    assert int(second[21]) == IGNORE_INDEX and int(second[22]) == IGNORE_INDEX and int(second[20]) == 0
    assert int(mtp_of(moved).counters["mtp_targets"]) == int(counters["mtp_targets"]) == int(jnp.sum(second != IGNORE_INDEX))
    # ... but it is an input of its own document's positions, so compare the first document's part alone
    first_doc = lambda t: mtp_of(jnp.concatenate([t[:24], jnp.zeros((41,), t.dtype)])).counters["mtp_loss"]  # noqa: E731
    assert float(first_doc(text)) == float(first_doc(moved))
