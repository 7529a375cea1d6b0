"""`afmoe` (models/afmoe.py) at a small size on the CPU, against the plain reference
(`benchmark/reference/afmoe.py`) on seeded weights: logits of packed rows holding documents
longer than the window and shorter than it, the loss and every leaf's gradient, three AdamW
steps through the trainer's own step; the two kinds of attention (a window layer rotates and
sees a band, a full layer takes no positions and sees its whole document: swap the kinds and the
output changes; the two named faults show against the reference); the gate on attention's
output; the embedding's multiplier; the shares of an expert layer adding up to the reference's
uncut layer with the shared expert counted once; the other families' lowered steps being what
they were; what the family refuses, from the one place the expert families share.

Tolerances: everything here is float32 under ``highest`` matmul precision on both sides, so
values agree to rounding in another order of summation: 2e-4 on logits of size ~1, 2e-5
relative on a loss, 2e-3 on a leaf's gradient norm and on its elements against the leaf's
largest (a near-tie of the router's may fall either way for a token-slot, which moves a routed
bank's row), 1e-4 on the layer's output in the share test."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_afmoe as W
from benchmark.reference import afmoe as reference
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict, get_config_class, get_model_class
from dolomite_engine_tpu.models.modeling_utils import Attention
from dolomite_engine_tpu.models.shared_expert_moe import STEP_COUNTERS, SharedExpertMoE
from dolomite_engine_tpu.ops.rope import RoPEParams, get_cos_sin
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import make_train_step

KINDS = ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"]
CFG = dict(
    model_type="afmoe", vocab_size=256, n_positions=64, n_embd=32, n_layer=5, n_head=4, num_key_value_heads=2, attention_head_dim=8,
    n_inner=48, layer_types=KINDS, sliding_window=12, num_dense_layers=1, rope_theta=10000,
    num_experts=32, num_experts_per_tok=4, experts_held=[8, 8], moe_intermediate_size=12, num_shared_experts=1, route_scale=2.826,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
    fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1,
)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
SWAPPED = ["full_attention" if kind == "sliding_attention" else "sliding_attention" for kind in KINDS]


def model_and_weights(cfg=CFG, seed=3, **kwargs):
    model = get_model_class("afmoe")(config=config_from_dict(cfg), **kwargs)
    weights = W.make_all(cfg, seed)
    # norm weights away from one, so that a norm in the wrong place or with the wrong weight shows
    for i, layer in enumerate(weights["layers"]):
        for name in ("q_norm_weight", "k_norm_weight") + W.NORMS:
            layer[name] = 1.0 + 0.3 * jnp.cos(jnp.arange(layer[name].shape[0], dtype=jnp.float32) + i + len(name))
    return model, weights, W.unrolled_program_tree(weights, cfg)


def packed_row(docs, seed=1, length=CFG["n_positions"], vocab=CFG["vocab_size"]):
    """[length + 1] tokens: documents of the given lengths, each ending in eos (0), the rest one more."""
    rng = np.random.default_rng(seed)
    text = rng.integers(1, vocab, size=length + 1).astype(np.int32)
    text[np.cumsum(docs) - 1] = 0
    return text


def wrapper_for(cfg=CFG, **kwargs):
    return ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="fp32", sequence_length=cfg["n_positions"],
        reset_attention_mask=True, reset_position_ids=True, zero_stage=0, **kwargs,
    )


def program_logits(model, params, text, cfg=CFG):
    batch = wrapper_for(cfg).prepare_inputs_and_labels(jnp.asarray(text)[None])
    return model.apply({"params": params}, batch["input_ids"], position_ids=batch["position_ids"], segment_ids=batch["segment_ids"]).logits[0]


def test_registered_under_its_model_type_and_the_seeded_weights_fit_the_program_tree():
    assert get_config_class("afmoe").__name__ == "AfmoeConfig"
    model, _, params = model_and_weights()
    assert type(model).__name__ == "AfmoeForCausalLM" and model.step_counter_names == STEP_COUNTERS  # (no splash kernel on the CPU)
    own = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), compute_loss=True))["params"])
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, own)) == jax.tree.leaves(jax.tree.map(lambda a: a.shape, params))
    # an untied head; four norms a block; a window layer and a full layer hold the same leaves; a shared expert
    assert set(own) == {"transformer", "lm_head"}
    assert set(own["transformer"]["h_0"]) == {"ln_1", "ln_1_out", "ln_2", "ln_2_out", "attn", "mlp"}
    for i in (1, 2):
        assert set(own["transformer"][f"h_{i}"]["attn"]) == {"c_attn", "g_proj", "c_proj", "q_norm_weight", "k_norm_weight"}
        assert set(own["transformer"][f"h_{i}"]["moe"]) == {"gate", "e_score_correction_bias", "c_fc", "c_proj", "shared_c_fc", "shared_c_proj"}
    names = W.leaves_by_name(params)
    assert len(names) == len(jax.tree.leaves(params)) and {"layer0.mlp_c_fc", "layer2.g_proj", "layer1.q_norm_weight", "layer4.shared_c_proj", "lm_head"} <= set(names)
    config = config_from_dict(CFG)
    assert config.head_dim == 8 and config.moe_shared_expert_intermediate_size == 12 and config.expert_layers == 4
    assert config.m_emb == pytest.approx(32**0.5) and config.norm_topk_prob_epsilon == 1e-20 and config.routed_scaling_factor == 2.826
    assert [config.layer_window(i) for i in range(5)] == [12, 12, None, 12, 12] and config.buffer_names == ("e_score_correction_bias",)
    record = config.layout_record()
    assert (record["blocks_window"], record["blocks_full"], record["blocks_dense"], record["blocks_experts"], record["experts_held"]) == (4, 1, 1, 4, 8)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == W.count_parameters(CFG)["total"]


# documents longer than the window (12) and shorter than it; in `odd_window` a window that no block of the kernel divides
ROWS = {"longer_than_the_window": (23, 41), "shorter_and_longer": (7, 40, 17), "every_document_inside_the_window": (11, 12, 9, 12, 10, 10)}


@pytest.mark.parametrize(
    "docs, window", [(docs, 12) for docs in ROWS.values()] + [(ROWS["shorter_and_longer"], 7)], ids=list(ROWS) + ["odd_window_7"]
)
def test_logits_of_a_packed_row_follow_the_reference(docs, window):
    cfg = dict(CFG, sliding_window=window)
    model, weights, params = model_and_weights(cfg)
    text = packed_row(docs)
    with jax.default_matmul_precision("highest"):
        mine = program_logits(model, params, text, cfg)
        ref = reference.forward_logits(cfg, weights, jnp.asarray(text[:-1]))
        np.testing.assert_allclose(mine, ref, rtol=2e-4, atol=2e-4)
        # and the documents do not see each other: a document alone gives its part of the row
        first = model.apply({"params": params}, jnp.asarray(text[None, : docs[0]])).logits
        np.testing.assert_allclose(mine[: docs[0]], first[0], rtol=2e-4, atol=2e-4)


def reference_loss_and_grads(weights, text, cfg=CFG, **faults):
    m = W.model_dims(cfg)

    def loss(p):
        loss_sum, z_sum, count, _ = reference.sequence_loss_terms(m, p, text, **faults)
        return (loss_sum + m["z_loss_coef"] * z_sum) / jnp.maximum(count, 1.0)

    return jax.value_and_grad(loss)(weights)


@pytest.mark.parametrize("docs", [ROWS["longer_than_the_window"], ROWS["shorter_and_longer"]], ids=["longer_than_the_window", "shorter_and_longer"])
def test_the_loss_and_every_leaf_s_gradient_follow_the_reference(docs):
    _, weights, params = model_and_weights()
    wrapper = wrapper_for(gradient_checkpointing_args={"checkpoint_every": 1})
    text = jnp.asarray(packed_row(docs))
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.value_and_grad(lambda p: wrapper.loss(p, text[None], train=True), has_aux=True)(params)
        ref_loss, ref_grads = reference_loss_and_grads(weights, text)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5)
    assert counters["held_expert_rows"].shape == (4, 8)
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


def test_the_trainer_s_step_follows_the_reference_and_holds_the_bias():
    """Three steps of `make_train_step` (the loss through `ModelWrapperForPretraining`, AdamW
    from `get_optimizer` with the routers' buffers held) against the reference's three steps:
    each loss, the first gradient's per-leaf norms, the parameters' change (the bias: none, weight
    decay or not), the counters of the four layers of experts."""
    seed = 11
    wrapper = wrapper_for(gradient_checkpointing_args={"checkpoint_every": 1})
    assert wrapper.step_counter_names == STEP_COUNTERS
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
    losses, rows, first_nu = [], [], None
    with jax.default_matmul_precision("highest"):
        for text in data:
            state, metrics = step(state, {"text": jnp.asarray(text)[None]}, jax.random.PRNGKey(0))
            losses.append(float(metrics["loss"]))
            rows.append(np.asarray(metrics["counters"]["held_expert_rows"]))
            if first_nu is None:
                adam = [s for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "nu")) if hasattr(s, "nu")][0]
                first_nu = adam.nu
    ref = reference.train_steps(CFG, seed, data, OPTIMIZER)

    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
    b2 = OPTIMIZER["betas"][1]
    grad_norms = {k: float(np.sqrt(np.sum(v) / (1 - b2))) for k, v in W.leaves_by_name(first_nu).items()}
    gap, where = compare.worst_leaf_gap(grad_norms, ref["grad_norms"])
    assert gap < 2e-3, (gap, where)
    delta = jax.tree.map(lambda a, b: a - b, state.params, start)
    delta_norms = {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in W.leaves_by_name(delta).items()}
    gap, where = compare.worst_leaf_gap(delta_norms, ref["delta_norms"])
    assert gap < 2e-3, (gap, where)
    for layer in (1, 2, 3, 4):  # the buffers stayed where the seed put them, weight decay or not
        assert delta_norms[f"layer{layer}.e_score_correction_bias"] == 0.0 == ref["delta_norms"][f"layer{layer}.e_score_correction_bias"]
    assert min(v for k, v in delta_norms.items() if "correction_bias" not in k) > 0
    for mine, facts in zip(rows, ref["routing"]):
        assert mine.shape == (4, 8)
        np.testing.assert_allclose(mine, np.asarray(facts["held_expert_rows"]), atol=2)  # a near-tie may fall either way


# ---- the two kinds of attention

def attention_layer(window, **overrides):
    config = config_from_dict(dict(CFG, **overrides))
    module = Attention(config=config, window=window)
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(2, 40, CFG["n_embd"])).astype(np.float32))
    segments = jnp.asarray(np.array([[1] * 29 + [2] * 11, [1] * 40], np.int32))
    positions = jnp.asarray(np.array([list(range(29)) + list(range(11)), list(range(40))], np.int32))
    cos_sin = get_cos_sin(RoPEParams.from_config(8, 10000, None, 64), positions)
    params = nn.unbox(module.init(jax.random.PRNGKey(2), h, segment_ids=segments, rope_cos_sin=cos_sin)["params"])
    return module, params, h, segments, cos_sin


def test_a_full_layer_ignores_positions_and_a_window_layer_uses_them():
    """The block hands a full layer no positions whatever the model computed, and a window
    layer the model's: the same block with the kinds swapped gives another output, a full layer's
    output does not move with `position_ids`, a window layer's does."""
    model, _, params = model_and_weights()
    swapped, _, _ = model_and_weights(dict(CFG, layer_types=SWAPPED))
    text = packed_row((23, 41))
    batch = wrapper_for().prepare_inputs_and_labels(jnp.asarray(text)[None])
    run = lambda m, pos: m.apply({"params": params}, batch["input_ids"], position_ids=pos, segment_ids=batch["segment_ids"]).logits  # noqa: E731
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(run(model, batch["position_ids"]) - run(swapped, batch["position_ids"])).max()) > 1e-2
        all_full, all_window = (model_and_weights(dict(CFG, layer_types=[kind] * 5))[0] for kind in ("full_attention", "sliding_attention"))
        np.testing.assert_array_equal(run(all_full, batch["position_ids"]), run(all_full, batch["position_ids"] + 5))
        # rope is relative: a shift of a whole row's positions moves nothing; a stretch does
        assert float(jnp.abs(run(all_window, batch["position_ids"]) - run(all_window, 2 * batch["position_ids"])).max()) > 1e-3


def test_the_window_is_itself_and_the_keys_before_it_inside_the_document():
    """`Attention.window` against the sum written out: query i of a document sees keys
    ``max(start, i - window + 1) .. i``; a window as long as the row is a full layer."""
    module, params, h, segments, cos_sin = attention_layer(5)
    full, _, _, _, _ = attention_layer(None)
    with jax.default_matmul_precision("highest"):
        mine, _ = module.apply({"params": params}, h, segment_ids=segments, rope_cos_sin=cos_sin)
        whole, _ = full.apply({"params": params}, h, segment_ids=segments, rope_cos_sin=cos_sin)
        long_window, _ = Attention(config=module.config, window=40).apply({"params": params}, h, segment_ids=segments, rope_cos_sin=cos_sin)
    np.testing.assert_allclose(long_window, whole, rtol=1e-6, atol=1e-6)
    # the first `window` tokens of a document see all of it so far: there the two layers agree; later they do not
    np.testing.assert_allclose(mine[0, :5], whole[0, :5], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mine[0, 29:34], whole[0, 29:34], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(mine[0, 10:29] - whole[0, 10:29]).max()) > 1e-3
    # a key further back than the window moves nothing: change token 3's input, and outputs from token 8 on stay (window 5)
    moved = h.at[1, 3].add(1.0)
    with jax.default_matmul_precision("highest"):
        other, _ = module.apply({"params": params}, moved, segment_ids=segments, rope_cos_sin=cos_sin)
    np.testing.assert_array_equal(other[1, 8:], mine[1, 8:])
    assert float(jnp.abs(other[1, 3:8] - mine[1, 3:8]).min(axis=0).max()) > 0


def test_the_gate_multiplies_the_heads_output_before_the_out_projection():
    module, params, h, segments, cos_sin = attention_layer(5)
    assert params["g_proj"]["kernel"].shape == (32, 32) and set(params["g_proj"]) == {"kernel"}  # as wide as the heads' output, no bias
    plain_config = config_from_dict(dict(CFG, model_type="gpt_dolomite", position_embedding_type="rope", attention_head_type="gqa", add_bias=False, qk_norm=True))
    plain = Attention(config=plain_config, window=5)
    ungated = {k: v for k, v in params.items() if k != "g_proj"}
    with jax.default_matmul_precision("highest"):
        mine, _ = module.apply({"params": params}, h, segment_ids=segments, rope_cos_sin=cos_sin)
        # a gate of sigmoid(0) = one half everywhere halves the layer's output
        halved = dict(params, g_proj={"kernel": jnp.zeros_like(params["g_proj"]["kernel"])})
        half, _ = module.apply({"params": halved}, h, segment_ids=segments, rope_cos_sin=cos_sin)
        without, _ = plain.apply({"params": ungated}, h, segment_ids=segments, rope_cos_sin=cos_sin)
    np.testing.assert_allclose(half, 0.5 * without, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(mine - half).max()) > 1e-3
    # written out: W_o (o * sigmoid(W_g h)), with o recovered from the ungated layer through an identity out-projection
    eye = dict(ungated, c_proj={"kernel": jnp.eye(32)})
    with jax.default_matmul_precision("highest"):
        heads_out, _ = plain.apply({"params": eye}, h, segment_ids=segments, rope_cos_sin=cos_sin)
        expected = (heads_out * jax.nn.sigmoid(h @ params["g_proj"]["kernel"])) @ params["c_proj"]["kernel"]
    np.testing.assert_allclose(mine, expected, rtol=1e-5, atol=1e-6)


FAULTS = {"kinds_swapped": dict(layer_types=SWAPPED), "a_full_layer_that_rotates": dict(rotate_full=True)}


@pytest.mark.parametrize("fault", FAULTS)
def test_the_two_named_faults_show_against_the_reference(fault):
    """A window layer run as a full layer (and the reverse), and a full layer that rotates: the
    reference with the fault against the program without it — logits, the loss and the first
    gradient's groups all move by far more than any limit of the cell."""
    model, weights, params = model_and_weights()
    wrapper = wrapper_for(gradient_checkpointing_args={"checkpoint_every": 1})
    text = jnp.asarray(packed_row((23, 41)))
    with jax.default_matmul_precision("highest"):
        mine = program_logits(model, params, np.asarray(text))
        faulty = reference.forward_logits(CFG, weights, text[:-1], **FAULTS[fault])
        assert float(jnp.abs(mine - faulty).max()) > 20 * 2e-4
        (loss, _), grads = jax.value_and_grad(lambda p: wrapper.loss(p, text[None], train=True), has_aux=True)(params)
        faulty_loss, faulty_grads = reference_loss_and_grads(weights, text, **FAULTS[fault])
    assert abs(float(loss) - float(faulty_loss)) > 20 * 2e-5 * float(loss)
    norms = lambda tree: {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in W.leaves_by_name(tree).items()}  # noqa: E731
    gap, where = compare.worst_leaf_gap(norms(grads), norms(W.unrolled_program_tree(faulty_grads, CFG)))
    assert gap > 0.01, (gap, where)


def test_the_embedding_is_multiplied_by_the_square_root_of_the_width():
    model, weights, params = model_and_weights()
    plain, _, _ = model_and_weights(dict(CFG, mup_enabled=False))
    assert plain.config.m_emb is None
    ids = jnp.asarray(packed_row((23, 41))[None, :-1])
    scaled = jax.tree.map(lambda a: a, params)
    scaled["transformer"]["wte"]["embedding"] = params["transformer"]["wte"]["embedding"] * 32**0.5
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(model.apply({"params": params}, ids).logits, plain.apply({"params": scaled}, ids).logits, rtol=1e-5, atol=1e-5)


# ---- the experts with a shared expert

def test_the_sixteen_shares_add_up_to_the_reference_s_uncut_layer_with_the_shared_expert_counted_once():
    """Sixteen shares of 8 of 128 experts (the deployment's split, at a small width): the
    shares' routed parts, added, and the shared expert ONCE, are the reference's layer with all
    128 experts."""
    base = dict(CFG, num_experts=128, num_experts_per_tok=8)
    cfg_all = dict(base, experts_held=None)
    m_all = W.model_dims(cfg_all)
    u = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48, CFG["n_embd"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        p_all = W.make_layer(cfg_all, 9, 2)
        whole = reference.experts(m_all, p_all, u[0])
        shared = reference.swiglu(u[0], p_all["shared_c_fc"], p_all["shared_c_proj"])
        total, rows = jnp.zeros_like(whole), 0
        for first in range(0, 128, 8):
            cfg = dict(base, experts_held=[first, 8])
            p = W.make_layer(cfg, 9, 2)
            assert p["c_fc"].shape == (8, 32, 24) and p["shared_c_fc"].shape == (32, 24)  # [held, d, up | gate]
            np.testing.assert_array_equal(p["c_fc"], p_all["c_fc"][first : first + 8])  # the share IS a slice
            np.testing.assert_array_equal(p["shared_c_fc"], p_all["shared_c_fc"])  # what every chip computes alike
            params = {"gate": p["gate"], "e_score_correction_bias": p["e_score_correction_bias"], **{k: {"kernel": p[k]} for k in ("c_fc", "c_proj", "shared_c_fc", "shared_c_proj")}}
            out, counters = SharedExpertMoE(config=config_from_dict(cfg)).apply({"params": params}, u)
            np.testing.assert_allclose(out[0], reference.experts(W.model_dims(cfg), p, u[0]), rtol=1e-4, atol=1e-5)
            total, rows = total + (out[0] - shared), rows + int(counters["routed_slots"])
    np.testing.assert_allclose(total + shared, whole, rtol=1e-4, atol=1e-5)
    assert rows == 48 * 8  # every token-slot was some share's


def test_the_renormalisation_is_the_family_s():
    from dolomite_engine_tpu.ops.moe import route_sigmoid_bias

    config = config_from_dict(CFG)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0, 0.0])
    weights, chosen = route_sigmoid_bias(logits, 2, bias, config.routed_scaling_factor, config.norm_topk_prob, config.norm_topk_prob_epsilon)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 4]  # the bias brought expert 4 in
    picked = jax.nn.sigmoid(logits[0])[chosen[0]]
    np.testing.assert_allclose(weights[0], 2.826 * picked / picked.sum(), rtol=1e-6)  # weighed without it, times route_scale


# ---- the other families' programs, and what this one refuses

COMMON = dict(resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
              fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1)
# sha256 of (the parameter tree, the lowered train step) and the step's lines, taken on the commit before this family (212dfd7)
PARENT_STEPS = {
    "ouro": (
        dict(model_type="ouro", vocab_size=256, n_positions=64, n_embd=32, n_layer=2, n_head=4, n_inner=48, total_ut_steps=4, rope_theta=1e6, **COMMON),
        "b8853daa465c2428ee01123becaae40f4e606c618102b5fd88a57f8bb7e94080", 3077, "39d711fe836f9d2642fcd83e8e7f1be3d097b9178088e406547a3b8694cd41f5",
    ),
    "lfm2_moe": (
        dict(model_type="lfm2_moe", vocab_size=256, n_positions=64, n_embd=32, n_layer=5, n_head=4, num_key_value_heads=2, n_inner=48,
             layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=1, conv_L_cache=3, rope_theta=1e6,
             num_experts=32, num_experts_per_tok=4, experts_held=[8, 8], moe_intermediate_size=12, routed_scaling_factor=1.0, **COMMON),
        "fc7c87885542e98aa56749b3e6a7539f93807f25092603fd1e49b840f3beefaf", 7800, "a553fd571935354fb599047c27b4c2023d2e572db75f3c69fc26d813decce770",
    ),
    "gpt_dolomite": (
        dict(model_type="gpt_dolomite", vocab_size=256, n_positions=64, n_embd=32, n_layer=2, n_head=4, num_key_value_heads=2, n_inner=48,
             attention_head_type="gqa", position_embedding_type="rope", activation_function="swiglu", normalization_function="rmsnorm", add_bias=False, **COMMON),
        "de94680e0fc8ef7a359ba9d96a7939ae553ec83eb80ea7fa490f7492b52ee8a4", 1903, "6a96f4765c3c02a4bed9513711bcab89867e0bdfb42ba4a84cd99ac8437cd478",
    ),
}


@pytest.mark.parametrize("family", PARENT_STEPS)
def test_the_lowered_step_of_a_family_that_shares_the_changed_code_is_what_it_was(family):
    """`Attention` (a window, a gate, positions by layer), `ops/attention` (a window in the mask,
    the tables and the dispatch) and the four-norm block (`modeling_utils.sandwich_normed_block`:
    Ouro's, moved) serve a further family since PR 40. The parameter tree and the lowered train
    step (bfloat16, `full` remat every block, `skip_nonfinite`) of a looped model, of the
    short-convolution hybrid and of the dense model at these sizes are, letter for letter, what
    the commit before lowered: the hashes were taken there, on this installation (jax 0.9.0).
    (`test_joyai_flash.py` and `test_nemotron_h.py` hold their families' the same way.) A
    change of one of these programs on purpose takes its hash anew, and says so."""
    from dolomite_engine_tpu.distributed import TrainState

    cfg, tree_hash, lines, text_hash = PARENT_STEPS[family]
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="bf16", sequence_length=cfg["n_positions"],
        reset_attention_mask=True, reset_position_ids=True, zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1},
    )
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=OPTIMIZER["lr"])
    optimizer = get_optimizer("TorchAdamW", {k: OPTIMIZER[k] for k in ("weight_decay", "betas", "eps")}, schedule, model_config=wrapper.config)

    def init():
        params = nn.unbox(wrapper.model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), compute_loss=True)["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params), fp8=None)

    state = jax.eval_shape(init)
    step = jax.jit(make_train_step(
        lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True), optimizer,
        gradient_clipping=1.0, skip_nonfinite=True, has_aux=bool(wrapper.step_counter_names),
    ))
    text = step.lower(
        state, {"text": jax.ShapeDtypeStruct((1, 2, cfg["n_positions"] + 1), jnp.int32)}, jax.ShapeDtypeStruct((2,), jnp.uint32)
    ).as_text()
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), state.params))
    assert hashlib.sha256(tree.encode()).hexdigest() == tree_hash
    assert len(text.splitlines()) == lines
    assert hashlib.sha256(text.encode()).hexdigest() == text_hash


def test_what_the_family_refuses(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    ids = jnp.zeros((1, 16), jnp.int32)
    scanned, _, _ = model_and_weights(scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers with afmoe.*attention's kind"):
        scanned.init(jax.random.PRNGKey(0), ids)
    model, _, params = model_and_weights()
    with pytest.raises(NotImplementedError, match="afmoe has no generation cache.*per-layer page budgets.*ROADMAP M6"):
        model.apply({"params": params}, ids, kv_caches=[None] * 5, cache_index=0)
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.init_kv_caches(1, 16)
    # ... and the layer itself: a cache under a window is refused where the cache would be written
    module, layer_params, h, segments, cos_sin = attention_layer(5)
    cache = {"k": jnp.zeros((2, 64, 2, 8)), "v": jnp.zeros((2, 64, 2, 8))}
    with pytest.raises(NotImplementedError, match="KV cache under a window of 5.*ROADMAP M6"):
        module.apply({"params": layer_params}, h, rope_cos_sin=cos_sin, kv_cache=cache, cache_index=0)
    with pytest.raises(ValueError, match="experts_held"):
        config_from_dict(dict(CFG, experts_held=[30, 8]))
    with pytest.raises(ValueError, match="names 4 layers"):
        config_from_dict(dict(CFG, layer_types=["full_attention"] * 4))
    with pytest.raises(ValueError, match="sliding_attention and full_attention"):
        config_from_dict(dict(CFG, layer_types=["conv"] + KINDS[1:]))
    with pytest.raises(ValueError, match="sliding_window 0"):
        config_from_dict(dict(CFG, sliding_window=0))
    with pytest.raises(ValueError, match="score_func"):
        config_from_dict(dict(CFG, score_func="softmax"))
    with pytest.raises(ValueError, match="qk_norm / attention_output_gate"):
        config_from_dict(dict(CFG, attention_output_gate=False))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        config_from_dict(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="position_embedding_type"):
        config_from_dict(dict(CFG, position_embedding_type="alibi"))
    for axis, kwargs in (("tp", dict(tensor_parallel_size=2)), ("ep", dict(expert_parallel_size=2))):
        MeshManager(**kwargs)
        try:
            with pytest.raises(ValueError, match=f"afmoe on a mesh with {axis} > 1"):
                model.init(jax.random.PRNGKey(0), ids)
        finally:
            MeshManager.destroy()


def test_the_trainer_s_counts_take_a_window_layer_s_keys_as_the_window():
    """`get_model_tflops` and `estimate_remat_activation_bytes` count a window layer's score and
    value products, and its kept scores, over ``min(s, window)`` keys a token; the gate's
    projection is a dot like the others."""
    from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes, get_model_tflops

    windowed = config_from_dict(dict(CFG, n_positions=4096, sliding_window=256))
    all_full = config_from_dict(dict(CFG, n_positions=4096, layer_types=["full_attention"] * 5))
    long_window = config_from_dict(dict(CFG, n_positions=4096, sliding_window=4096))
    b, s, heads, d = 2, 4096, 4, 8
    saved = 4 * (4 * b * s * (s - 256) * heads * d)  # four window layers, each 4 b s (s - window) n d fewer forward
    assert all_full.forward_block_flops(b, s) - windowed.forward_block_flops(b, s) == saved
    assert long_window.forward_block_flops(b, s) == all_full.forward_block_flops(b, s)
    args = dict(gradient_checkpointing_method="block", gradient_checkpointing_args={"checkpoint_every": 1, "policy": "full"})
    assert get_model_tflops(all_full, b, s, **args) - get_model_tflops(windowed, b, s, **args) == pytest.approx(4 * saved / 1e12)  # forward, backward x 2, replay
    dots = dict(gradient_checkpointing_method="block", gradient_checkpointing_args={"checkpoint_every": 1, "policy": "save_dots"})
    kept = lambda config: estimate_remat_activation_bytes(config, b, s, **dots)["activation_bytes_per_replica"]  # noqa: E731
    assert kept(all_full) - kept(windowed) == 4 * b * s * 4 * heads * (s - 256)  # the scores of four layers, a band each
    ungated = config_from_dict(dict(CFG, model_type="gpt_dolomite", position_embedding_type="rope", attention_head_type="gqa", n_positions=4096))
    assert not hasattr(ungated, "layer_window") and not ungated.attention_output_gate
