"""`joyai_llm_flash` (models/joyai_flash.py) at a small size on the CPU, against the plain
reference (`benchmark/reference/joyai_flash.py`) on seeded weights: logits of packed rows, both
parts of the loss and every leaf's gradient, three AdamW steps through the trainer's own step;
the de-interleaved rotation against the literal one; scores over a wider head than the values
through `sdpa` and through the splash kernel (interpreted); the shares of an expert layer
adding up to the reference's uncut layer; what multi-token prediction masks and adds; what the
family refuses.

Tolerances: everything here is float32 under ``highest`` matmul precision on both sides, so
values agree to rounding in another order of summation: 2e-4 on logits of size ~1 (the tower's
test's), 2e-5 relative on a loss, 2e-3 on a leaf's gradient norm and on its elements against
the leaf's largest (a near-tie of the router's may fall either way for a token-slot, which
moves a routed bank's row), 1e-4 on the layer's output in the share test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_joyai_flash as W
from benchmark.reference import joyai_flash as reference
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict, get_config_class, get_model_class
from dolomite_engine_tpu.models.joyai_flash import LOSS_PARTS, second_token_labels
from dolomite_engine_tpu.models.shared_expert_moe import STEP_COUNTERS, SharedExpertMoE
from dolomite_engine_tpu.ops.loss import IGNORE_INDEX
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import make_train_step

CFG = dict(
    model_type="joyai_llm_flash", vocab_size=256, n_positions=64, n_embd=32, n_layer=3, n_head=4, n_inner=48,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=32e6,
    first_k_dense_replace=1, num_experts=32, num_experts_per_tok=4, experts_held=[8, 8], moe_intermediate_size=12,
    n_shared_experts=1, routed_scaling_factor=2.5, num_nextn_predict_layers=1, mtp_loss_coef=0.3,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
    fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1,
)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)


def model_and_weights(cfg=CFG, seed=3, **kwargs):
    model = get_model_class("joyai_llm_flash")(config=config_from_dict(cfg), **kwargs)
    weights = W.make_all(cfg, seed)
    return model, weights, W.unrolled_program_tree(weights, cfg)


def packed_row(docs, seed=1, length=CFG["n_positions"]):
    """[length + 1] tokens: documents of the given lengths, each ending in eos (0), the rest one more."""
    rng = np.random.default_rng(seed)
    text = rng.integers(1, CFG["vocab_size"], size=length + 1).astype(np.int32)
    text[np.cumsum(docs) - 1] = 0
    return text


def wrapper_for(cfg=CFG, **kwargs):
    return ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="fp32", sequence_length=cfg["n_positions"],
        reset_attention_mask=True, reset_position_ids=True, zero_stage=0, **kwargs,
    )


def test_registered_under_its_model_type_and_the_seeded_weights_fit_the_program_tree():
    assert get_config_class("joyai_llm_flash").__name__ == "JoyAIFlashConfig"
    model, _, params = model_and_weights()
    assert type(model).__name__ == "JoyAIFlashForCausalLM" and model.step_counter_names == STEP_COUNTERS + LOSS_PARTS
    own = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), compute_loss=True))["params"])
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, own)) == jax.tree.leaves(jax.tree.map(lambda a: a.shape, params))
    names = W.leaves_by_name(params)
    assert len(names) == len(jax.tree.leaves(params)) and "layer3.mtp_eh_proj" in names and "layer0.mlp_c_fc" in names
    config = config_from_dict(CFG)
    assert config.head_dim == 12 and config.moe_shared_expert_intermediate_size == 12 and config.expert_layers == 3
    assert config.layout_record()["blocks_experts"] == 2 and config.layout_record()["experts_held"] == 8


@pytest.mark.parametrize("docs", [(23, 41), (10, 37, 17)], ids=["two_documents", "three_documents"])
def test_logits_of_a_packed_row_follow_the_reference(docs):
    model, weights, params = model_and_weights()
    wrapper = wrapper_for()
    text = packed_row(docs)
    batch = wrapper.prepare_inputs_and_labels(jnp.asarray(text)[None])
    with jax.default_matmul_precision("highest"):
        mine = model.apply({"params": params}, batch["input_ids"], position_ids=batch["position_ids"], segment_ids=batch["segment_ids"]).logits
        ref = reference.forward_logits(CFG, weights, jnp.asarray(text[:-1]))
        np.testing.assert_allclose(mine[0], ref, rtol=2e-4, atol=2e-4)
        # and the documents do not see each other: a document alone gives its part of the row
        first = model.apply({"params": params}, batch["input_ids"][:, : docs[0]]).logits
        np.testing.assert_allclose(mine[:, : docs[0]], first, rtol=2e-4, atol=2e-4)


def reference_loss_and_grads(weights, text):
    m = W.model_dims(CFG)
    counts = [jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0) for mask in reference.label_masks(m, text)]

    def loss(p):
        main, second, _ = reference.sequence_loss_terms(m, p, text)
        main_loss = (main[0] + m["z_loss_coef"] * main[1]) / counts[0]
        mtp_loss = (second[0] + m["z_loss_coef"] * second[1]) / counts[1]
        return main_loss + m["mtp_coef"] * mtp_loss, (main_loss, mtp_loss)

    return jax.value_and_grad(loss, has_aux=True)(weights)


@pytest.mark.parametrize("docs", [(23, 41), (10, 37, 17)], ids=["two_documents", "three_documents"])
def test_both_losses_and_every_leaf_s_gradient_follow_the_reference(docs):
    _, weights, params = model_and_weights()
    wrapper = wrapper_for(gradient_checkpointing_args={"checkpoint_every": 1})
    text = jnp.asarray(packed_row(docs))
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.value_and_grad(lambda p: wrapper.loss(p, text[None], train=True), has_aux=True)(params)
        (ref_loss, (ref_main, ref_mtp)), ref_grads = reference_loss_and_grads(weights, text)
    np.testing.assert_allclose([loss, counters["main_loss"], counters["mtp_loss"]], [ref_loss, ref_main, ref_mtp], rtol=2e-5)
    assert int(counters["mtp_targets"]) == int(jnp.sum(reference.label_masks(W.model_dims(CFG), text)[1]))
    mine, ref = W.leaves_by_name(grads), W.leaves_by_name(W.unrolled_program_tree(ref_grads, CFG))
    assert set(mine) == set(ref)
    for name, leaf in ref.items():
        if name.endswith("e_score_correction_bias"):
            assert float(jnp.abs(mine[name]).max()) == 0.0 == float(jnp.abs(leaf).max())  # a buffer: no gradient reaches it
            continue
        assert float(jnp.abs(leaf).max()) > 0, name
        np.testing.assert_allclose(mine[name], leaf, rtol=2e-3, atol=2e-3 * float(jnp.abs(leaf).max()), err_msg=name)


def batches(steps=3, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        text = rng.integers(1, CFG["vocab_size"], size=(rows, CFG["n_positions"] + 1)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 60, size=2)] = 0  # document boundaries (eos)
        out.append(text)
    return out


def test_the_trainer_s_step_follows_the_reference():
    """Three steps of `make_train_step` (the loss through `ModelWrapperForPretraining`, AdamW
    from `get_optimizer` with the routers' buffers held) against the reference's three steps:
    each loss and its two parts, the first gradient's per-leaf norms, the parameters' change,
    the counters of the three layers of experts (the MTP module's last)."""
    seed = 11
    wrapper = wrapper_for(gradient_checkpointing_args={"checkpoint_every": 1})
    assert wrapper.step_counter_names == STEP_COUNTERS + LOSS_PARTS
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=OPTIMIZER["lr"])
    optimizer = get_optimizer(
        "TorchAdamW", {k: OPTIMIZER[k] for k in ("weight_decay", "betas", "eps")}, schedule, model_config=wrapper.config,
    )
    from dolomite_engine_tpu.distributed import TrainState

    start = W.unrolled_program_tree(W.make_all(CFG, seed), CFG)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=start, opt_state=optimizer.init(start), fp8=None)
    step = jax.jit(make_train_step(
        lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True), optimizer,
        gradient_clipping=OPTIMIZER["gradient_clipping"], has_aux=True,
    ))
    data = batches()
    losses, parts, rows, first_nu = [], [], [], None
    with jax.default_matmul_precision("highest"):
        for text in data:
            state, metrics = step(state, {"text": jnp.asarray(text)[None]}, jax.random.PRNGKey(0))
            losses.append(float(metrics["loss"]))
            parts.append((float(metrics["counters"]["main_loss"]), float(metrics["counters"]["mtp_loss"])))
            rows.append(np.asarray(metrics["counters"]["held_expert_rows"]))
            if first_nu is None:
                adam = [s for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "nu")) if hasattr(s, "nu")][0]
                first_nu = adam.nu
    ref = reference.train_steps(CFG, seed, data, OPTIMIZER)

    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
    np.testing.assert_allclose(parts, list(zip(ref["main_losses"], ref["mtp_losses"])), rtol=2e-5)
    np.testing.assert_allclose(losses, [a + 0.3 * b for a, b in parts], rtol=1e-6)
    b2 = OPTIMIZER["betas"][1]
    grad_norms = {k: float(np.sqrt(np.sum(v) / (1 - b2))) for k, v in W.leaves_by_name(first_nu).items()}
    gap, where = compare.worst_leaf_gap(grad_norms, ref["grad_norms"])
    assert gap < 2e-3, (gap, where)
    delta = jax.tree.map(lambda a, b: a - b, state.params, start)
    delta_norms = {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in W.leaves_by_name(delta).items()}
    gap, where = compare.worst_leaf_gap(delta_norms, ref["delta_norms"])
    assert gap < 2e-3, (gap, where)
    for layer in (1, 2, 3):  # the buffers stayed where the seed put them, weight decay or not
        assert delta_norms[f"layer{layer}.e_score_correction_bias"] == 0.0 == ref["delta_norms"][f"layer{layer}.e_score_correction_bias"]
    assert min(v for k, v in delta_norms.items() if "correction_bias" not in k) > 0
    for mine, facts in zip(rows, ref["routing"]):
        assert mine.shape == (3, 8)
        np.testing.assert_allclose(mine, np.asarray(facts["held_expert_rows"]), atol=2)  # a near-tie may fall either way


def test_accumulated_micro_batches_add_their_counts_and_average_the_loss_s_parts():
    _, _, params = model_and_weights()
    wrapper = wrapper_for()
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


# ---- the experts

def test_the_shares_add_up_to_the_reference_s_uncut_layer():
    """Four shares of 8 experts with gated (SwiGLU) banks: the routed parts of all shares plus
    the shared expert, counted once, are the reference's layer with all 32 experts."""
    cfg_all = dict(CFG, experts_held=None)
    m_all = W.model_dims(cfg_all)
    p_all = W.make_layer(cfg_all, 5, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (40, CFG["n_embd"]))
    with jax.default_matmul_precision("highest"):
        whole = reference.experts(m_all, p_all, u)
        shared = reference.experts(dict(m_all, held=0), p_all, u)  # no routed expert: the shared one alone
        total = jnp.zeros_like(whole)
        for first in range(0, 32, 8):
            cfg = dict(CFG, experts_held=[first, 8])
            p = W.make_layer(cfg, 5, 1)
            assert p["c_fc"].shape == (8, 32, 24)  # [held, d, up | gate]
            np.testing.assert_array_equal(p["c_fc"], p_all["c_fc"][first : first + 8])  # the share IS a slice
            moe = W.unrolled_program_tree({"outer": W.make_outer(cfg, 5), "layers": [W.make_layer(cfg, 5, i) for i in range(4)]}, cfg)
            out, counters = SharedExpertMoE(config=config_from_dict(cfg)).apply({"params": moe["transformer"]["h_1"]["moe"]}, u[None])
            total = total + (out[0] - shared)
            assert int(counters["routed_slots"]) + int(counters["absent_slots"]) == 40 * 4
    np.testing.assert_allclose(total + shared, whole, rtol=1e-4, atol=1e-5)


# ---- multi-token prediction

def test_second_token_labels_leave_out_what_crosses_a_document():
    #           doc 1: 5 7 0 | doc 2: 9 4 6 0 | doc 3: 8 ...
    text = jnp.asarray([[5, 7, 0, 9, 4, 6, 0, 8, 3]])
    wrapper = wrapper_for()
    batch = wrapper.prepare_inputs_and_labels(text)
    X = IGNORE_INDEX
    np.testing.assert_array_equal(batch["labels"], [[7, 0, X, 4, 6, 0, X, 3]])
    np.testing.assert_array_equal(second_token_labels(batch["labels"], batch["segment_ids"]), [[0, X, X, 6, 0, X, X, X]])
    np.testing.assert_array_equal(second_token_labels(batch["labels"], None), [[0, X, 4, 6, 0, X, 3, X]])
    main, second = reference.label_masks({"eos": 0}, text[0])
    np.testing.assert_array_equal(second, [True, False, False, True, True, False, False, False])
    np.testing.assert_array_equal(main, batch["labels"][0] != X)


def test_mtp_targets_across_a_boundary_carry_no_loss_and_a_zero_coefficient_leaves_the_main_loss_and_gradient():
    _, _, params = model_and_weights()
    text = jnp.asarray(packed_row((23, 41)))
    with_mtp, without = wrapper_for(), wrapper_for(dict(CFG, mtp_loss_coef=0.0))
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


def test_what_the_family_refuses(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    ids = jnp.zeros((1, 16), jnp.int32)
    scanned, _, _ = model_and_weights(scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers with joyai_llm_flash"):
        scanned.init(jax.random.PRNGKey(0), ids)
    model, _, params = model_and_weights()
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.apply({"params": params}, ids, kv_caches=[None] * 3, cache_index=0)
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.init_kv_caches(1, 16)
    with pytest.raises(ValueError, match="experts_held"):
        config_from_dict(dict(CFG, experts_held=[30, 8]))
    with pytest.raises(ValueError, match="depth 1"):
        config_from_dict(dict(CFG, num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="position_embedding_type"):
        config_from_dict(dict(CFG, position_embedding_type="alibi"))
    for axis, kwargs in (("tp", dict(tensor_parallel_size=2)), ("ep", dict(expert_parallel_size=2))):
        MeshManager(**kwargs)
        try:
            with pytest.raises(ValueError, match=f"{axis} > 1"):
                model.init(jax.random.PRNGKey(0), ids)
        finally:
            MeshManager.destroy()


def test_the_lowered_step_is_what_it_was_before_a_third_family_shared_its_expert_layer():
    """`SharedExpertMoE`, `route_sigmoid_bias`, the rope+QKV seam and the families' raises serve a
    third family since PR 33 (no shared expert, an epsilon of its own, QK norms); this family's
    parameter tree and its lowered train step (bfloat16, `full` remat every block, `skip_nonfinite`,
    counters beside the loss) at this file's size are, letter for letter, what the commit before
    lowered: the hashes were taken there, on this installation (jax 0.9.0). A change of this
    family's program on purpose takes them anew, and says so: PR 34 did — the expert layer's
    gather, weighted scatter-add and their transposes became loops over blocks of the routed rows
    with rules of their own (`ops/moe._dispatch_rows`, `_combine_rows`), so the step's text was
    taken anew there (8214 lines before); PR 37 did again — the activation between the grouped
    products walks blocks of rows up to the last routed one (`ops/moe._activate_rows`) and the
    group sizes are read off the sorted keys, so the only operations that differ stand under
    `moe_dispatch` and `moe_experts` or in the unnamed helpers called from there (8862 lines
    before); PR 39 did a third time — the head's logits are computed once, in both passes through
    the head: the chunked loss's summed rule forms both gradients in its differentiated forward
    over a token block's kept logits and its backward rule only scales them
    (`ops/loss._chunked_ce_terms`), so what differs stands under `head_loss` (9264 lines before);
    the parameter tree's hash is the one PR 33 took."""
    import hashlib

    from dolomite_engine_tpu.distributed import TrainState

    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=CFG, dtype="bf16", sequence_length=CFG["n_positions"],
        reset_attention_mask=True, reset_position_ids=True, zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1},
    )
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=OPTIMIZER["lr"])
    optimizer = get_optimizer(
        "TorchAdamW", {k: OPTIMIZER[k] for k in ("weight_decay", "betas", "eps")}, schedule, model_config=wrapper.config,
    )

    def init():
        params = nn.unbox(wrapper.model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), compute_loss=True)["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params), fp8=None)

    state = jax.eval_shape(init)
    step = jax.jit(make_train_step(
        lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True), optimizer,
        gradient_clipping=1.0, skip_nonfinite=True, has_aux=True,
    ))
    text = step.lower(
        state, {"text": jax.ShapeDtypeStruct((1, 2, CFG["n_positions"] + 1), jnp.int32)}, jax.ShapeDtypeStruct((2,), jnp.uint32)
    ).as_text()
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), state.params))
    assert hashlib.sha256(tree.encode()).hexdigest() == "4f3c47802e75a87cbdc4288b995f51a06c50485f568ee733f27eb4296b8d833b"
    assert len(text.splitlines()) == 8979
    assert hashlib.sha256(text.encode()).hexdigest() == "c86fd38c4d2ebde4f9169ec711ea30c7a3bb05972644b0b649ba51eef1995b9a"
