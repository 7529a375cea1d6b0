"""The plain reference against the package's model at a tiny size on the CPU: the same seeded
weights (benchmark/weights.py) through both must give the same logits and the same packed
loss in float32, for MHA and for GQA, and the fp8 control must move them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as W
from benchmark.program_layout import leaves_by_name, unrolled_program_tree
from benchmark.reference import gpt_dense

BASE = dict(
    model_type="gpt_dolomite", vocab_size=384, n_positions=128, n_embd=64, n_layer=2, n_head=4,
    num_key_value_heads=None, attention_head_type="mha", n_inner=96, activation_function="swiglu",
    normalization_function="rmsnorm", position_embedding_type="rope", rope_theta=10000, add_bias=False,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, tie_word_embeddings=True, bos_token_id=0,
    eos_token_id=0, pad_token_id=0, fused_lm_head_loss=True, z_loss_coef=1.0e-4,
)
CONFIGS = {
    "mha": BASE,
    "gqa": dict(BASE, num_key_value_heads=2, attention_head_type="gqa"),
}


def packed_row(seed: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    row = rng.integers(1, vocab, size=length)
    row[[17, 18, 60, length - 2]] = 0  # documents end (eos), one of them empty, one at the edge
    return row.astype(np.int32)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def setup(request):
    from dolomite_engine_tpu.enums import AttentionImplementation, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining

    cfg = CONFIGS[request.param]
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=dict(cfg), model_class="AutoModelForCausalLM", dtype="fp32",
        attention_implementation=AttentionImplementation.sdpa, use_padding_free_transformer=True,
        reset_attention_mask=True, reset_position_ids=True, sequence_length=96, micro_batch_size=1,
    )
    weights = W.make_all(cfg, 2**31 + 3, jnp.float32)
    return cfg, wrapper, weights, unrolled_program_tree(weights)


def test_logits_agree_with_the_package(setup):
    cfg, wrapper, weights, params = setup
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], size=40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = wrapper.model.apply({"params": params}, jnp.asarray(tokens)[None]).logits[0]
    mine = gpt_dense.forward_logits(cfg, weights, tokens)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=2e-5)


def test_packed_loss_and_gradient_agree_with_the_package(setup):
    cfg, wrapper, weights, params = setup
    row = packed_row(1, 97, cfg["vocab_size"])
    m = W.model_dims(cfg)
    with jax.default_matmul_precision("highest"):
        theirs, their_grads = jax.value_and_grad(lambda p: wrapper.loss(p, jnp.asarray(row)[None], train=True))(params)
        mine, my_grads = jax.value_and_grad(lambda w: gpt_dense.batch_loss(m, w, jnp.asarray(row)[None]))(weights)
    assert float(mine) == pytest.approx(float(theirs), abs=2e-5)
    their_norms = {k: float(jnp.linalg.norm(v)) for k, v in leaves_by_name(their_grads).items()}
    my_norms = {k: float(v) for k, v in gpt_dense.leaf_norms(my_grads).items()}
    assert set(their_norms) == set(my_norms)
    for name, value in their_norms.items():
        assert my_norms[name] == pytest.approx(value, rel=1e-3, abs=1e-7), name


def test_attention_does_not_cross_documents(setup):
    cfg, _, weights, _ = setup
    m = W.model_dims(cfg)
    row = packed_row(2, 97, cfg["vocab_size"])
    other = row.copy()
    other[:17] = np.random.default_rng(9).integers(1, cfg["vocab_size"], size=17)  # another first document

    def tail_terms(text):
        # the loss terms of the last document only: mask the labels of everything before it
        tokens, labels = text[:-1], text[1:]
        segments, positions = gpt_dense.segments_from_eos(jnp.asarray(tokens), m["eos"])
        h = weights["outer"]["wte"][tokens]
        for p in weights["layers"]:
            h = gpt_dense.layer(m, p, h, positions, segments)
        return np.asarray(h[61:])  # hidden states after the third eos (position 60)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(tail_terms(row), tail_terms(other), atol=1e-6)


def test_fp8_control_moves_the_logits_and_keeps_gradients_flowing(setup):
    cfg, _, weights, _ = setup
    m = W.model_dims(cfg)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], size=40).astype(np.int32)
    exact = gpt_dense.forward_logits(cfg, weights, tokens)
    control = gpt_dense.forward_logits(cfg, weights, tokens, quant="fp8")
    relative = float(jnp.linalg.norm(control - exact) / jnp.linalg.norm(exact))
    assert 5e-3 < relative < 0.3
    row = packed_row(3, 97, cfg["vocab_size"])
    grads = jax.grad(lambda w: gpt_dense.batch_loss(m, w, jnp.asarray(row)[None], quant="fp8"))(weights)
    assert all(float(v) > 0 for v in gpt_dense.leaf_norms(grads).values())
    with pytest.raises(ValueError, match="unknown control precision"):
        gpt_dense.matmul(jnp.ones((2, 2)), jnp.ones((2, 2)), quant="int4")


def test_weights_depend_on_seed_and_layer_only():
    cfg = CONFIGS["gqa"]
    one = W.make_layer(cfg, 2**31 + 3, 1, jnp.float32)
    again = jax.jit(lambda i: W.make_layer(cfg, 2**31 + 3, i, jnp.float32))(jnp.asarray(1))
    whole = W.make_all(cfg, 2**31 + 3, jnp.float32)["layers"][1]
    for name in one:
        # to the last bit or two: a jitted and an eager draw fuse the scaling differently
        np.testing.assert_allclose(np.asarray(one[name]), np.asarray(again[name]), rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(np.asarray(one[name]), np.asarray(whole[name]), rtol=1e-6, atol=1e-9)
    other_seed = W.make_layer(cfg, 3, 1, jnp.float32)  # 2**31 + 3 and 3 share their low 31 bits
    assert not np.array_equal(np.asarray(one["c_attn"]), np.asarray(other_seed["c_attn"]))
    assert one["c_attn"].shape == (64, (4 + 2 * 2) * 16)
    assert float(jnp.std(one["attn_c_proj"])) == pytest.approx(0.02 / np.sqrt(2 * 2), rel=0.05)
