"""GPTDolomite numerical tests.

Parity: reference `tests/hf_models/single_gpu/gpt_dolomite_test.py` — attention-implementation
equivalence matrix over head-type x position-embedding, KV-cache generation consistency,
padding-free (segment-ids) vs batched equivalence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import AttentionImplementation
from dolomite_engine_tpu.models.gpt_dolomite import GPTDolomiteForCausalLM

from ..test_commons import assert_allclose, get_dense_test_config, get_dummy_inputs

HEAD_TYPES = ["mha", "mqa", "gqa"]
POS_EMBS = ["learned_absolute", "alibi", "rope", "nope"]


def _build(config, attention_implementation=AttentionImplementation.sdpa, **kwargs):
    model = GPTDolomiteForCausalLM(
        config=config, attention_implementation=attention_implementation, **kwargs
    )
    ids, mask = get_dummy_inputs(config)
    params = model.init(jax.random.PRNGKey(0), ids)
    return model, params, ids, mask


@pytest.mark.parametrize("head_type", HEAD_TYPES)
@pytest.mark.parametrize("pos_emb", POS_EMBS)
def test_eager_sdpa_equivalence(head_type, pos_emb):
    config = get_dense_test_config(head_type, pos_emb)
    model, params, ids, mask = _build(config)

    out_sdpa = model.apply(params, ids, attention_mask=mask)
    model_eager = GPTDolomiteForCausalLM(
        config=config, attention_implementation=AttentionImplementation.eager
    )
    out_eager = model_eager.apply(params, ids, attention_mask=mask)

    valid = np.asarray(mask).astype(bool)
    assert_allclose(
        np.asarray(out_sdpa.logits)[valid],
        np.asarray(out_eager.logits)[valid],
        atol=2e-4,
        rtol=2e-4,
    )


@pytest.mark.parametrize("head_type", HEAD_TYPES)
def test_loss_matches_manual_shift(head_type):
    config = get_dense_test_config(head_type, "rope", normalization_function="rmsnorm")
    model, params, ids, _ = _build(config)
    out = model.apply(params, ids, compute_loss=True)

    logits = np.asarray(out.logits, np.float32)
    logprobs = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    manual = -np.mean(
        [
            np.asarray(logprobs)[b, t, ids[b, t + 1]]
            for b in range(ids.shape[0])
            for t in range(ids.shape[1] - 1)
        ]
    )
    assert_allclose(out.loss, manual, atol=1e-5, rtol=1e-5)


def test_packed_segment_equivalence():
    """Packed two-document row with segment ids == two separate rows (padding-free parity)."""
    config = get_dense_test_config("mqa", "rope")
    model = GPTDolomiteForCausalLM(config=config)

    rs = np.random.RandomState(0)
    doc_a = rs.randint(0, config.vocab_size, (1, 8)).astype(np.int32)
    doc_b = rs.randint(0, config.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(doc_a))

    packed_ids = jnp.concatenate([jnp.asarray(doc_a), jnp.asarray(doc_b)], axis=1)
    segment_ids = jnp.asarray([[1] * 8 + [2] * 8])
    position_ids = jnp.asarray([list(range(8)) + list(range(8))])
    out_packed = model.apply(
        params, packed_ids, position_ids=position_ids, segment_ids=segment_ids
    )

    out_a = model.apply(params, jnp.asarray(doc_a))
    out_b = model.apply(params, jnp.asarray(doc_b))

    assert_allclose(out_packed.logits[:, :8], out_a.logits, atol=2e-4, rtol=2e-4)
    assert_allclose(out_packed.logits[:, 8:], out_b.logits, atol=2e-4, rtol=2e-4)


def test_kv_cache_decode_matches_full_forward():
    config = get_dense_test_config("gqa", "rope")
    model = GPTDolomiteForCausalLM(config=config)
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, config.vocab_size, (2, 12)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), ids)

    full = model.apply(params, ids)

    # prefill 8, then decode 4 one by one
    caches = model.init_kv_caches(2, 12)
    prefill = model.apply(
        params, ids[:, :8], kv_caches=caches, cache_index=jnp.zeros((), jnp.int32)
    )
    logits = [prefill.logits]
    caches = prefill.kv_caches
    for t in range(8, 12):
        step = model.apply(
            params,
            ids[:, t : t + 1],
            kv_caches=caches,
            cache_index=jnp.asarray(t, jnp.int32),
        )
        caches = step.kv_caches
        logits.append(step.logits)

    decoded = jnp.concatenate(logits, axis=1)
    assert_allclose(decoded, full.logits, atol=3e-4, rtol=3e-4)


def test_mup_multipliers_applied():
    config = get_dense_test_config(
        "mqa", "rope", m_emb=2.0, m_width=4.0, m_residual=0.5, init_method="mup"
    )
    model, params, ids, _ = _build(config)
    out = model.apply(params, ids)
    assert out.logits.shape == (*ids.shape, config.vocab_size)
    assert bool(jnp.all(jnp.isfinite(out.logits)))


def test_tied_and_untied_lm_head():
    tied = get_dense_test_config("mqa", "rope")
    untied = get_dense_test_config("mqa", "rope", tie_word_embeddings=False)
    m1, p1, ids, _ = _build(tied)
    m2, p2, _, _ = _build(untied)
    assert "lm_head" not in p1["params"]
    assert "lm_head" in p2["params"]
    assert m2.apply(p2, ids).logits.shape == m1.apply(p1, ids).logits.shape


def test_checkpoint_policy_remat_is_numerics_identical():
    """gradient_checkpointing_args.checkpoint_policy maps to jax.checkpoint_policies and
    changes rematerialization only: loss AND grads are bit-identical to no-remat; unknown
    names fail loudly with the valid list."""
    config = get_dense_test_config("mqa", "rope")
    ids, _ = get_dummy_inputs(config, padded=False)

    results = {}
    for name, kwargs in [
        ("none", {}),
        ("block", dict(checkpoint_every=1)),
        ("dots", dict(checkpoint_every=1, checkpoint_policy="dots_saveable")),
        # the named-policy vocabulary (gradient_checkpointing_args.policy): every
        # policy must be a pure remat-schedule change — same loss, ulp-same grads
        ("full", dict(checkpoint_every=1, checkpoint_policy="full")),
        ("save_dots", dict(checkpoint_every=1, checkpoint_policy="save_dots")),
        (
            "save_attention_out",
            dict(checkpoint_every=1, checkpoint_policy="save_attention_out"),
        ),
        # offload_dots falls back to save_dots off-TPU (no pinned_host) with a warning;
        # numerics are policy-independent either way
        ("offload_dots", dict(checkpoint_every=1, checkpoint_policy="offload_dots")),
        ("every_2_save_dots", dict(checkpoint_every=2, checkpoint_policy="save_dots")),
    ]:
        model = GPTDolomiteForCausalLM(config=config, **kwargs)
        params = model.init(jax.random.PRNGKey(0), ids)

        def loss_fn(p):
            return model.apply(p, ids, labels=ids, compute_loss=True).loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        flat = jax.flatten_util.ravel_pytree(grads)[0]
        results[name] = (float(loss), np.asarray(flat))

    for name in results:
        if name == "none":
            continue
        assert results[name][0] == results["none"][0], name
        # grads: this container's CPU XLA reassociates one fusion differently under remat,
        # costing 1 ulp on ~30% of elements (verified identical on unmodified seed code);
        # assert to float32-ulp tolerance instead of bitwise so the property under test —
        # remat changes rematerialization only, not math — still binds tightly
        np.testing.assert_allclose(
            results[name][1], results["none"][1], rtol=0, atol=1.2e-7, err_msg=name
        )

    with pytest.raises(ValueError, match="unknown checkpoint_policy"):
        GPTDolomiteForCausalLM(
            config=config, checkpoint_every=1, checkpoint_policy="nope"
        ).init(jax.random.PRNGKey(0), ids)


def test_the_lowered_step_is_what_it_was():
    """The dense family shares `Attention`, `ops/attention` and the head with the families of
    `family_contract.py`, and is pinned beside them (`PINNED_STEPS`)."""
    from .family_contract import check_the_lowered_step

    check_the_lowered_step("gpt_dolomite")
