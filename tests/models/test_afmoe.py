"""`afmoe` (models/afmoe.py) at a small size on the CPU, against the plain reference
(`benchmark/reference/afmoe.py`) on seeded weights. The family's contract — registered, logits of
packed rows holding documents longer than the window and shorter than it, the loss and every leaf's
gradient, three AdamW steps through the trainer's own step, the shares of an expert layer adding up
to the reference's uncut layer with the shared expert counted once, what the family refuses, the
lowered step — is `family_contract.py`'s; here is what is the family's own: what its tree holds; the
two kinds of attention (a window layer rotates and sees a band, a full layer takes no positions and
sees its whole document: swap the kinds and the output changes; the two named faults show against
the reference); the gate on attention's output; the embedding's multiplier; the renormalisation;
a cache refused under a window; what the trainer counts for a window layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_afmoe as W
from benchmark.reference import afmoe as reference
from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.modeling_utils import Attention
from dolomite_engine_tpu.ops.rope import RoPEParams, get_cos_sin

from .family_contract import (
    AFMOE_KINDS as KINDS,
    FAMILIES,
    built,
    contract_tests,
    leaf_norms,
    model_of,
    packed_row,
    program_logits,
    program_loss_and_grads,
    program_tree,
    reference_loss_and_grads,
    text_of,
    wrapper_for,
)

CFG = FAMILIES["afmoe"].cfg
SWAPPED = ["full_attention" if kind == "sliding_attention" else "sliding_attention" for kind in KINDS]
globals().update(contract_tests("afmoe"))


def test_the_tree_holds_four_norms_a_block_a_gate_and_a_shared_expert():
    own, (_, _, params, _) = program_tree("afmoe"), built("afmoe")
    # an untied head; four norms a block; a window layer and a full layer hold the same leaves; a shared expert
    assert set(own) == {"transformer", "lm_head"}
    assert set(own["transformer"]["h_0"]) == {"ln_1", "ln_1_out", "ln_2", "ln_2_out", "attn", "mlp"}
    for i in (1, 2):
        assert set(own["transformer"][f"h_{i}"]["attn"]) == {"c_attn", "g_proj", "c_proj", "q_norm_weight", "k_norm_weight"}
        assert set(own["transformer"][f"h_{i}"]["moe"]) == {"gate", "e_score_correction_bias", "c_fc", "c_proj", "shared_c_fc", "shared_c_proj"}
    assert {"layer0.mlp_c_fc", "layer2.g_proj", "layer1.q_norm_weight", "layer4.shared_c_proj", "lm_head"} <= set(W.leaves_by_name(params))
    config = config_from_dict(CFG)
    assert config.head_dim == 8 and config.moe_shared_expert_intermediate_size == 12 and config.expert_layers == 4
    assert config.m_emb == pytest.approx(32**0.5) and config.norm_topk_prob_epsilon == 1e-20 and config.routed_scaling_factor == 2.826
    assert [config.layer_window(i) for i in range(5)] == [12, 12, None, 12, 12] and config.buffer_names == ("e_score_correction_bias",)
    record = config.layout_record()
    assert (record["blocks_window"], record["blocks_full"], record["blocks_dense"], record["blocks_experts"], record["experts_held"]) == (4, 1, 1, 4, 8)


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
    model, _, params, _ = built("afmoe")
    swapped = model_of("afmoe", dict(CFG, layer_types=SWAPPED))
    text = packed_row((23, 41))
    batch = wrapper_for(CFG).prepare_inputs_and_labels(jnp.asarray(text)[None])
    run = lambda m, pos: m.apply({"params": params}, batch["input_ids"], position_ids=pos, segment_ids=batch["segment_ids"]).logits  # noqa: E731
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(run(model, batch["position_ids"]) - run(swapped, batch["position_ids"])).max()) > 1e-2
        all_full, all_window = (model_of("afmoe", dict(CFG, layer_types=[kind] * 5)) for kind in ("full_attention", "sliding_attention"))
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
    case = "longer_than_the_window"
    _, weights, _, _ = built("afmoe")
    text = text_of(FAMILIES["afmoe"].gradient_rows[case])[0]
    (loss, _), grads = program_loss_and_grads("afmoe", case)
    with jax.default_matmul_precision("highest"):
        faulty = reference.forward_logits(CFG, weights, text[:-1], **FAULTS[fault])
    assert float(jnp.abs(program_logits("afmoe", case) - faulty).max()) > 20 * 2e-4
    (faulty_loss, _), faulty_grads = reference_loss_and_grads("afmoe", case, **FAULTS[fault])
    assert abs(float(loss) - float(faulty_loss)) > 20 * 2e-5 * float(loss)
    family = FAMILIES["afmoe"]
    gap, where = compare.worst_leaf_gap(leaf_norms(family, grads), leaf_norms(family, W.unrolled_program_tree(faulty_grads, CFG)))
    assert gap > 0.01, (gap, where)


def test_the_embedding_is_multiplied_by_the_square_root_of_the_width():
    model, _, params, _ = built("afmoe")
    plain = model_of("afmoe", dict(CFG, mup_enabled=False))
    assert plain.config.m_emb is None
    ids = jnp.asarray(packed_row((23, 41))[None, :-1])
    scaled = jax.tree.map(lambda a: a, params)
    scaled["transformer"]["wte"]["embedding"] = params["transformer"]["wte"]["embedding"] * 32**0.5
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(model.apply({"params": params}, ids).logits, plain.apply({"params": scaled}, ids).logits, rtol=1e-5, atol=1e-5)


# ---- the router

def test_the_renormalisation_is_the_family_s():
    from dolomite_engine_tpu.ops.moe import route_sigmoid_bias

    config = config_from_dict(CFG)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0, 0.0])
    weights, chosen = route_sigmoid_bias(logits, 2, bias, config.routed_scaling_factor, config.norm_topk_prob, config.norm_topk_prob_epsilon)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 4]  # the bias brought expert 4 in
    picked = jax.nn.sigmoid(logits[0])[chosen[0]]
    np.testing.assert_allclose(weights[0], 2.826 * picked / picked.sum(), rtol=1e-6)  # weighed without it, times route_scale


def test_a_cache_under_a_window_is_refused_where_the_cache_would_be_written():
    module, layer_params, h, _, cos_sin = attention_layer(5)
    cache = {"k": jnp.zeros((2, 64, 2, 8)), "v": jnp.zeros((2, 64, 2, 8))}
    with pytest.raises(NotImplementedError, match="KV cache under a window of 5.*ROADMAP M6"):
        module.apply({"params": layer_params}, h, rope_cos_sin=cos_sin, kv_cache=cache, cache_index=0)


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
