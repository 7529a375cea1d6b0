"""`lfm2_moe` (models/lfm2_moe.py) at a small size on the CPU, against the plain reference
(`benchmark/reference/lfm2_moe.py`) on seeded weights: logits of packed rows, the loss and every
leaf's gradient, three AdamW steps through the trainer's own step; a document's tokens leaving
every other document's logits bit for bit (taps and attention both); `ShortConv` against a
token-by-token loop; the QK norm before the rotation (and not after); the bias choosing and not
weighing; the shares of an expert layer adding up to the reference's uncut layer, with no
shared expert to count once; the tied head; what the family refuses, from the one place the
expert families share.

Tolerances: everything here is float32 under ``highest`` matmul precision on both sides, so
values agree to rounding in another order of summation: 2e-4 on logits of size ~1, 2e-5
relative on a loss, 2e-3 on a leaf's gradient norm and on its elements against the leaf's
largest (a near-tie of the router's may fall either way for a token-slot, which moves a routed
bank's row), 1e-4 on the layer's output in the share test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_lfm2_moe as W
from benchmark.reference import lfm2_moe as reference
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict, get_config_class, get_model_class
from dolomite_engine_tpu.models.lfm2_moe import ShortConv
from dolomite_engine_tpu.models.shared_expert_moe import STEP_COUNTERS, SharedExpertMoE
from dolomite_engine_tpu.ops.rope import RoPEParams, apply_rotary_pos_emb, get_cos_sin, split_qkv_apply_rope
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import make_train_step

CFG = dict(
    model_type="lfm2_moe", vocab_size=256, n_positions=64, n_embd=32, n_layer=5, n_head=4, num_key_value_heads=2, n_inner=48,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=1, conv_L_cache=3, rope_theta=1e6,
    num_experts=32, num_experts_per_tok=4, experts_held=[8, 8], moe_intermediate_size=12, routed_scaling_factor=1.0,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
    fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1,
)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)


def model_and_weights(cfg=CFG, seed=3, **kwargs):
    model = get_model_class("lfm2_moe")(config=config_from_dict(cfg), **kwargs)
    weights = W.make_all(cfg, seed)
    # norm weights away from one, so that a norm in the wrong place or with the wrong weight shows
    for i, layer in enumerate(weights["layers"]):
        for name in ("q_norm_weight", "k_norm_weight", "ln_1", "ln_2"):
            if name in layer:
                layer[name] = 1.0 + 0.3 * jnp.cos(jnp.arange(layer[name].shape[0], dtype=jnp.float32) + i + len(name))
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
    assert get_config_class("lfm2_moe").__name__ == "Lfm2MoeConfig"
    model, _, params = model_and_weights()
    assert type(model).__name__ == "Lfm2MoeForCausalLM" and model.step_counter_names == STEP_COUNTERS
    own = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), compute_loss=True))["params"])
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, own)) == jax.tree.leaves(jax.tree.map(lambda a: a.shape, params))
    # a tied head over the table: no head parameter; no shared expert: no parameters of one
    assert set(own) == {"transformer"} and set(own["transformer"]["h_1"]["moe"]) == {"gate", "e_score_correction_bias", "c_fc", "c_proj"}
    assert set(own["transformer"]["h_0"]) == {"ln_1", "conv", "ln_2", "mlp"} and set(own["transformer"]["h_1"]["attn"]) == {"c_attn", "c_proj", "q_norm_weight", "k_norm_weight"}
    names = W.leaves_by_name(params)
    assert len(names) == len(jax.tree.leaves(params)) and {"layer0.mlp_c_fc", "layer0.conv_weight", "layer1.q_norm_weight", "layer4.c_proj"} <= set(names)
    config = config_from_dict(CFG)
    assert config.head_dim == 8 and config.moe_shared_expert_intermediate_size == 0 and config.expert_layers == 4
    record = config.layout_record()
    assert (record["blocks_conv"], record["blocks_attention"], record["blocks_dense"], record["blocks_experts"], record["experts_held"]) == (4, 1, 1, 4, 8)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == W.count_parameters(CFG)["total"]


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


@pytest.mark.parametrize("changed", [0, 1, 2])
def test_changing_one_document_s_tokens_leaves_every_other_document_s_logits_bit_for_bit(changed):
    """Taps and attention both: the three documents of a row, the tokens of one replaced (its
    eos kept), and the logits of the other two are the same bits."""
    model, _, params = model_and_weights()
    wrapper = wrapper_for()
    docs = (10, 37, 17)
    text = packed_row(docs)
    other = text.copy()
    start = int(np.cumsum((0,) + docs)[changed])
    other[start : start + docs[changed] - 1] = (other[start : start + docs[changed] - 1] + 7) % 255 + 1
    run = jax.jit(lambda ids, pos, seg: model.apply({"params": params}, ids, position_ids=pos, segment_ids=seg).logits)
    logits = []
    for row in (text, other):
        batch = wrapper.prepare_inputs_and_labels(jnp.asarray(row)[None])
        logits.append(np.asarray(run(batch["input_ids"], batch["position_ids"], batch["segment_ids"]))[0])
    inside = np.zeros(CFG["n_positions"], bool)
    inside[start : start + docs[changed]] = True
    np.testing.assert_array_equal(logits[0][~inside], logits[1][~inside])
    assert np.abs(logits[0][inside] - logits[1][inside]).max() > 1e-3


def reference_loss_and_grads(weights, text):
    m = W.model_dims(CFG)

    def loss(p):
        loss_sum, z_sum, count, _ = reference.sequence_loss_terms(m, p, text)
        return (loss_sum + m["z_loss_coef"] * z_sum) / jnp.maximum(count, 1.0)

    return jax.value_and_grad(loss)(weights)


@pytest.mark.parametrize("docs", [(23, 41), (10, 37, 17)], ids=["two_documents", "three_documents"])
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


# ---- the operator's pieces

def test_short_conv_is_the_token_by_token_loop():
    """``[B | C | u] = W_in h; z = B u; c_t = sum_k w[:, k] z_{t-(2-k)}`` inside the document;
    ``W_out (C c)``: written as a loop over tokens with an explicit window of three."""
    config = config_from_dict(CFG)
    rng = np.random.default_rng(5)
    hidden, taps, length = CFG["n_embd"], 3, 24
    h = rng.normal(size=(2, length, hidden)).astype(np.float32)
    segments = np.array([[1] * 9 + [2] * 2 + [3] * 13, [1] * 24], np.int32)
    module = ShortConv(config=config)
    params = nn.unbox(module.init(jax.random.PRNGKey(1), jnp.asarray(h), jnp.asarray(segments))["params"])
    assert set(params) == {"in_proj", "conv_weight", "out_proj"} and params["conv_weight"].shape == (hidden, taps)
    assert set(params["in_proj"]) == {"kernel"}  # no bias
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(module.apply({"params": params}, jnp.asarray(h), jnp.asarray(segments)))
    w_in, w, w_out = (
        np.asarray(leaf, np.float64) for leaf in (params["in_proj"]["kernel"], params["conv_weight"], params["out_proj"]["kernel"])
    )
    expected = np.zeros_like(h, dtype=np.float64)
    for row in range(2):
        window = []  # the last z's of this document
        for t in range(length):
            if t and segments[row, t] != segments[row, t - 1]:
                window = []
            projected = h[row, t].astype(np.float64) @ w_in
            gate_in, gate_out, x = projected[:hidden], projected[hidden : 2 * hidden], projected[2 * hidden :]
            window = (window + [gate_in * x])[-taps:]
            c = sum(w[:, taps - 1 - back] * z for back, z in enumerate(reversed(window)))
            expected[row, t] = (gate_out * c) @ w_out
    np.testing.assert_allclose(mine, expected, rtol=1e-4, atol=1e-6)
    # no activation anywhere: the operator is cubic in its input's scale
    with jax.default_matmul_precision("highest"):
        doubled = np.asarray(module.apply({"params": params}, jnp.asarray(2 * h), jnp.asarray(segments)))
    np.testing.assert_allclose(doubled, 8 * mine, rtol=1e-4, atol=1e-6)


def test_qk_norm_sits_between_the_split_and_the_rotation():
    """The seam normalises every query and key head over its columns and THEN rotates; with the
    two swapped the result differs (a weight that is not constant over a head's columns does not
    commute with the rotation), and the values are never normed."""
    rng = np.random.default_rng(2)
    heads, kv, head, seq = 4, 2, 8, 6
    qkv = jnp.asarray(rng.normal(size=(1, seq, (heads + 2 * kv) * head)).astype(np.float32))
    q_weight = jnp.asarray(1.0 + 0.5 * rng.normal(size=head).astype(np.float32))
    k_weight = jnp.asarray(1.0 + 0.5 * rng.normal(size=head).astype(np.float32))
    cos_sin = get_cos_sin(RoPEParams.from_config(head, 1e6, None, 64), jnp.arange(3, 3 + seq)[None])
    query, key, value = split_qkv_apply_rope(qkv, heads, kv, head, cos_sin, (q_weight, k_weight, 1e-5))
    plain_q, plain_k, plain_v = split_qkv_apply_rope(qkv, heads, kv, head, None)

    def norm(x, weight):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-5) * weight

    np.testing.assert_allclose(query, apply_rotary_pos_emb(norm(plain_q, q_weight), *cos_sin), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(key, apply_rotary_pos_emb(norm(plain_k, k_weight), *cos_sin), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(value, plain_v)
    swapped = norm(apply_rotary_pos_emb(plain_q, *cos_sin), q_weight)
    assert float(jnp.abs(swapped - query).max()) > 1e-2
    # without the norms the seam is what it was
    rotated, _, _ = split_qkv_apply_rope(qkv, heads, kv, head, cos_sin)
    np.testing.assert_array_equal(rotated, apply_rotary_pos_emb(plain_q, *cos_sin))


def test_the_fused_rope_kernel_steps_aside_for_the_norms_and_says_so(monkeypatch):
    """Where the fused rope+QKV kernel is promoted, a call with norms takes the split -> norm ->
    rotate form and writes ``rope_qkv_plan`` once; a call without them still takes the kernel."""
    from dolomite_engine_tpu.ops.pallas import kernel_overrides
    from dolomite_engine_tpu.utils import telemetry

    events = []

    class Recorder:
        def event_once(self, name, **fields):
            events.append((name, fields))

    monkeypatch.setattr(telemetry, "get_telemetry", lambda: Recorder())
    rng = np.random.default_rng(3)
    heads, kv, head, seq = 4, 2, 64, 8
    qkv = jnp.asarray(rng.normal(size=(1, seq, (heads + 2 * kv) * head)).astype(np.float32))
    weights = (jnp.full((head,), 1.5), jnp.full((head,), 0.5), 1e-5)
    cos_sin = get_cos_sin(RoPEParams.from_config(head, 1e6, None, 64), jnp.arange(seq)[None])
    expected = split_qkv_apply_rope(qkv, heads, kv, head, cos_sin, weights)
    with kernel_overrides(fused_rope_qkv="pallas"):
        stepped_aside = split_qkv_apply_rope(qkv, heads, kv, head, cos_sin, weights)
        through_kernel = split_qkv_apply_rope(qkv, heads, kv, head, cos_sin)
    for a, b in zip(stepped_aside, expected):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(through_kernel[0], split_qkv_apply_rope(qkv, heads, kv, head, cos_sin)[0], rtol=1e-5, atol=1e-5)
    (event,) = [fields for name, fields in events if name == "rope_qkv_plan"]
    assert event["form"] == "xla" and "between the split and the rotation" in event["why_xla"] and event["heads"] == (4, 2, 64)


# ---- the experts without a shared expert

def test_the_bias_chooses_and_does_not_weigh_and_the_denominator_is_the_family_s():
    from dolomite_engine_tpu.ops.moe import route_sigmoid_bias

    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0, 0.0])
    weights, chosen = route_sigmoid_bias(logits, 2, bias, 1.0, True, 1e-6)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 4]  # the bias brought expert 4 in
    scores = jax.nn.sigmoid(logits[0])
    picked = scores[chosen[0]]
    np.testing.assert_allclose(weights[0], picked / (picked.sum() + 1e-6), rtol=1e-7)  # weighed without it, + 1e-6
    tiny = jnp.full((1, 6), -40.0)  # scores of ~4e-18: the 1e-6 is the whole denominator, the 1e-20 hardly any of it
    w6, _ = route_sigmoid_bias(tiny, 2, jnp.zeros(6), 1.0, True, 1e-6)
    w20, _ = route_sigmoid_bias(tiny, 2, jnp.zeros(6), 1.0, True)
    assert float(w6.sum()) < 1e-10 and float(w20.sum()) > 0.9
    gradient = jax.grad(lambda b: route_sigmoid_bias(logits, 2, b, 1.0, True, 1e-6)[0].sum())(bias)
    assert float(jnp.abs(gradient).max()) == 0.0
    config = config_from_dict(CFG)
    assert config.norm_topk_prob_epsilon == 1e-6 and config.buffer_names == ("e_score_correction_bias",)


def test_the_shares_add_up_to_the_reference_s_uncut_layer():
    """Four shares of 8 experts with gated (SwiGLU) banks and NO shared expert: the shares'
    outputs, added, are the reference's layer with all 32 experts — nothing to count once."""
    cfg_all = dict(CFG, experts_held=None)
    m_all = W.model_dims(cfg_all)
    u = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48, CFG["n_embd"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        p_all = W.make_layer(cfg_all, 9, 2)
        whole = reference.experts(m_all, p_all, u[0])
        total, rows = jnp.zeros_like(whole), 0
        for first in (0, 8, 16, 24):
            cfg = dict(CFG, experts_held=[first, 8])
            p = W.make_layer(cfg, 9, 2)
            assert p["c_fc"].shape == (8, 32, 24) and "shared_c_fc" not in p  # [held, d, up | gate]
            np.testing.assert_array_equal(p["c_fc"], p_all["c_fc"][first : first + 8])  # the share IS a slice
            params = {"gate": p["gate"], "e_score_correction_bias": p["e_score_correction_bias"], "c_fc": {"kernel": p["c_fc"]}, "c_proj": {"kernel": p["c_proj"]}}
            out, counters = SharedExpertMoE(config=config_from_dict(cfg)).apply({"params": params}, u)
            np.testing.assert_allclose(out[0], reference.experts(W.model_dims(cfg), p, u[0]), rtol=1e-4, atol=1e-5)
            total, rows = total + out[0], rows + int(counters["routed_slots"])
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert rows == 48 * CFG["num_experts_per_tok"]  # every token-slot was some share's


def test_the_head_is_the_embedding_s_table():
    model, _, params = model_and_weights()
    assert "lm_head" not in params
    ids = jnp.asarray(packed_row((23, 41))[None, :-1])
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, ids).logits
        moved = jax.tree.map(lambda a: a, params)
        moved["transformer"]["wte"]["embedding"] = params["transformer"]["wte"]["embedding"].at[7].multiply(2.0)
        other = model.apply({"params": moved}, jnp.where(ids == 7, 8, ids)).logits  # token 7 is no input here
    np.testing.assert_allclose(other[..., 7], 2 * logits[..., 7], rtol=1e-5, atol=1e-6)


def test_what_the_family_refuses(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    ids = jnp.zeros((1, 16), jnp.int32)
    scanned, _, _ = model_and_weights(scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers with lfm2_moe"):
        scanned.init(jax.random.PRNGKey(0), ids)
    model, _, params = model_and_weights()
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.apply({"params": params}, ids, kv_caches=[None] * 5, cache_index=0)
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.init_kv_caches(1, 16)
    with pytest.raises(ValueError, match="experts_held"):
        config_from_dict(dict(CFG, experts_held=[30, 8]))
    with pytest.raises(ValueError, match="names 4 layers"):
        config_from_dict(dict(CFG, layer_types=["conv"] * 4))
    with pytest.raises(ValueError, match="conv and full_attention"):
        config_from_dict(dict(CFG, layer_types=["conv", "sliding_attention", "conv", "conv", "conv"]))
    with pytest.raises(ValueError, match="conv_bias"):
        config_from_dict(dict(CFG, conv_bias=True))
    with pytest.raises(ValueError, match="use_expert_bias"):
        config_from_dict(dict(CFG, use_expert_bias=False))
    with pytest.raises(ValueError, match="position_embedding_type"):
        config_from_dict(dict(CFG, position_embedding_type="alibi"))
    for axis, kwargs in (("tp", dict(tensor_parallel_size=2)), ("ep", dict(expert_parallel_size=2))):
        MeshManager(**kwargs)
        try:
            with pytest.raises(ValueError, match=f"{axis} > 1"):
                model.init(jax.random.PRNGKey(0), ids)
        finally:
            MeshManager.destroy()
