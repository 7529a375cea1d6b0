"""`lfm2_moe` (models/lfm2_moe.py) at a small size on the CPU, against the plain reference
(`benchmark/reference/lfm2_moe.py`) on seeded weights. The family's contract — registered, logits
of packed rows, the loss and every leaf's gradient, three AdamW steps through the trainer's own
step, the shares of an expert layer adding up to the reference's uncut layer (no shared expert to
count once), what the family refuses, the lowered step — is `family_contract.py`'s; here is what
is the family's own: what its tree holds; a document's tokens leaving every other document's logits
bit for bit (taps and attention both); `ShortConv` against a token-by-token loop; the QK norm before
the rotation (and not after); the bias choosing and not weighing; the tied head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.lfm2_moe import ShortConv
from dolomite_engine_tpu.ops.rope import RoPEParams, apply_rotary_pos_emb, get_cos_sin, split_qkv_apply_rope

from .family_contract import FAMILIES, built, contract_tests, packed_row, program_tree, wrapper_for

CFG = FAMILIES["lfm2_moe"].cfg
globals().update(contract_tests("lfm2_moe"))


def test_a_tied_head_no_shared_expert_and_one_operator_a_block():
    own, (_, _, params, _) = program_tree("lfm2_moe"), built("lfm2_moe")
    # a tied head over the table: no head parameter; no shared expert: no parameters of one
    assert set(own) == {"transformer"} and set(own["transformer"]["h_1"]["moe"]) == {"gate", "e_score_correction_bias", "c_fc", "c_proj"}
    assert set(own["transformer"]["h_0"]) == {"ln_1", "conv", "ln_2", "mlp"} and set(own["transformer"]["h_1"]["attn"]) == {"c_attn", "c_proj", "q_norm_weight", "k_norm_weight"}
    assert {"layer0.mlp_c_fc", "layer0.conv_weight", "layer1.q_norm_weight", "layer4.c_proj"} <= set(FAMILIES["lfm2_moe"].W.leaves_by_name(params))
    config = config_from_dict(CFG)
    assert config.head_dim == 8 and config.moe_shared_expert_intermediate_size == 0 and config.expert_layers == 4
    record = config.layout_record()
    assert (record["blocks_conv"], record["blocks_attention"], record["blocks_dense"], record["blocks_experts"], record["experts_held"]) == (4, 1, 1, 4, 8)


@pytest.mark.parametrize("changed", [0, 1, 2])
def test_changing_one_document_s_tokens_leaves_every_other_document_s_logits_bit_for_bit(changed):
    """Taps and attention both: the three documents of a row, the tokens of one replaced (its
    eos kept), and the logits of the other two are the same bits."""
    model, _, params, _ = built("lfm2_moe")
    wrapper = wrapper_for(CFG)
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


def test_the_head_is_the_embedding_s_table():
    model, _, params, _ = built("lfm2_moe")
    assert "lm_head" not in params
    ids = jnp.asarray(packed_row((23, 41))[None, :-1])
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, ids).logits
        moved = jax.tree.map(lambda a: a, params)
        moved["transformer"]["wte"]["embedding"] = params["transformer"]["wte"]["embedding"].at[7].multiply(2.0)
        other = model.apply({"params": moved}, jnp.where(ids == 7, 8, ids)).logits  # token 7 is no input here
    np.testing.assert_allclose(other[..., 7], 2 * logits[..., 7], rtol=1e-5, atol=1e-6)
