"""`nemotron_h` (models/nemotron_h.py) at a small size on the CPU: packed documents against
the documents run apart; the program against the plain reference
(`benchmark/reference/nemotron_h_tower.py`) on seeded weights — loss, per-leaf gradient
norms, three AdamW steps through the trainer's own step; the shares of an expert layer
adding up to the reference's uncut layer; what the family refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_nemotron_h as W
from benchmark.reference import nemotron_h_tower as reference
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict, get_config_class, get_model_class
from dolomite_engine_tpu.models.nemotron_h import SharedExpertMoE
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import make_train_step

CFG = dict(
    model_type="nemotron_h", vocab_size=256, n_positions=64, n_embd=32, n_layer=5, hybrid_override_pattern="MEM*E",
    n_head=4, num_key_value_heads=2, attention_head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    num_experts=32, num_experts_per_tok=6, experts_held=[8, 8], moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
    fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1,
)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
DOCS = (10, 37, 17)


def model_and_params(cfg=CFG, seed=3, **kwargs):
    model = get_model_class("nemotron_h")(config=config_from_dict(cfg), **kwargs)
    params = W.unrolled_program_tree(W.make_all(cfg, seed), cfg)
    return model, params


def test_registered_under_its_model_type():
    assert get_config_class("nemotron_h").__name__ == "NemotronHConfig"
    assert get_model_class("nemotron_h").__name__ == "NemotronHForCausalLM"


def test_seeded_weights_fit_the_program_tree():
    model, params = model_and_params()
    own = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"])
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, own)) == jax.tree.leaves(jax.tree.map(lambda a: a.shape, params))


def test_a_packed_row_is_its_documents_run_apart():
    """State, convolution taps and attention all reset: the logits of a document inside a
    packed row are those of the document alone (`M`, `E` and `*` layers all in the pattern)."""
    model, params = model_and_params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, sum(DOCS)), 1, CFG["vocab_size"])
    seg = jnp.asarray(np.repeat([1, 2, 3], DOCS))[None]
    packed = model.apply({"params": params}, ids, segment_ids=seg).logits
    start = 0
    for length in DOCS:
        alone = model.apply({"params": params}, ids[:, start : start + length]).logits
        np.testing.assert_allclose(packed[:, start : start + length], alone, rtol=2e-4, atol=2e-4)
        start += length
    unreset = model.apply({"params": params}, ids).logits
    assert float(jnp.abs(unreset - packed)[:, DOCS[0] :].max()) > 1e-3


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
    from `get_optimizer` with the router's buffer held) against the reference's three steps:
    each loss, the first gradient's per-leaf norms, the parameters' change, the counters."""
    seed = 11
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=CFG, dtype="fp32", sequence_length=CFG["n_positions"],
        reset_attention_mask=True, zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1},
    )
    assert wrapper.step_counter_names
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
    # the buffer stayed where the seed put it, weight decay or not; everything else moved
    assert delta_norms["layer1.e_score_correction_bias"] == 0.0 == ref["delta_norms"]["layer1.e_score_correction_bias"]
    assert min(v for k, v in delta_norms.items() if "correction_bias" not in k) > 0
    for mine, facts in zip(rows, ref["routing"]):
        np.testing.assert_allclose(mine, np.asarray(facts["held_expert_rows"]), atol=2)  # a near-tie may fall either way


def test_the_tower_is_what_it_was_before_its_expert_layer_moved():
    """`SharedExpertMoE` serves a second family from `models/shared_expert_moe.py` (PR 30); the
    tower's parameter tree and its lowered train step (bfloat16, `full` remat every layer,
    `skip_nonfinite`, counters beside the loss) at this file's size are, letter for letter, what
    the commit before the move lowered: the hashes were taken there, on this installation (jax
    0.9.0). A change of the tower's program on purpose takes them anew, and says so: PR 34 did —
    the expert layer's gather, weighted scatter-add and their transposes became loops over blocks
    of the routed rows with rules of their own (`ops/moe._dispatch_rows`, `_combine_rows`), so the
    step's text was taken anew there (6527 lines before); PR 37 did again — the activation between
    the grouped products walks blocks of rows up to the last routed one (`ops/moe._activate_rows`)
    and the group sizes are read off the sorted keys, so the only operations that differ stand under
    `moe_dispatch` and `moe_experts` or in the unnamed helpers called from there (6970 lines
    before); PR 39 did a third time — the head's logits are computed once: the chunked loss's
    summed rule forms both gradients in its differentiated forward over a token block's kept
    logits and its backward rule only scales them (`ops/loss._chunked_ce_terms`), so what differs
    stands under `head_loss` (7270 lines before); the parameter tree's hash is the first."""
    import hashlib

    from dolomite_engine_tpu.distributed import TrainState

    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=CFG, dtype="bf16", sequence_length=CFG["n_positions"],
        reset_attention_mask=True, zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1},
    )
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=OPTIMIZER["lr"])
    optimizer = get_optimizer(
        "TorchAdamW", {k: OPTIMIZER[k] for k in ("weight_decay", "betas", "eps")}, schedule, model_config=wrapper.config,
    )

    def init():
        params = nn.unbox(wrapper.model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"])
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
    assert hashlib.sha256(tree.encode()).hexdigest() == "36990d468b39e5c040180d1e45a25dac74eeeb1d513dc6bc37c03b12fe9fca5b"
    assert len(text.splitlines()) == 7104
    assert hashlib.sha256(text.encode()).hexdigest() == "dfec910f7fc86d2ca344ad7ad9e65ee52dde2439295c3b1aa2d148931e9bc880"
    from dolomite_engine_tpu.models import nemotron_h, shared_expert_moe

    assert nemotron_h.SharedExpertMoE is shared_expert_moe.SharedExpertMoE is SharedExpertMoE


def test_the_shares_add_up_to_the_reference_s_uncut_layer():
    """Four shares of 8 experts: the routed parts of all shares plus the shared expert, once,
    are the reference's layer with all 32 experts."""
    cfg_all = dict(CFG, experts_held=None)
    m_all = W.model_dims(cfg_all)
    p_all = W.make_layer(cfg_all, 5, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (40, CFG["n_embd"]))
    with jax.default_matmul_precision("highest"):
        whole = reference.experts_mixer(m_all, p_all, u)
        shared = reference.experts_mixer(dict(m_all, held=0), p_all, u)  # no routed expert: the shared one alone
        total = jnp.zeros_like(whole)
        for first in range(0, 32, 8):
            cfg = dict(CFG, experts_held=[first, 8])
            p = W.make_layer(cfg, 5, 1)
            np.testing.assert_array_equal(p["c_fc"], p_all["c_fc"][first : first + 8])  # the share IS a slice
            moe = W.unrolled_program_tree({"outer": W.make_outer(cfg, 5), "layers": [W.make_layer(cfg, 5, i) for i in range(5)]}, cfg)
            out, counters = SharedExpertMoE(config=config_from_dict(cfg)).apply(
                {"params": moe["transformer"]["h_1"]["moe"]}, u[None]
            )
            total = total + (out[0] - shared)
            assert int(counters["routed_slots"]) + int(counters["absent_slots"]) == 40 * 6
    np.testing.assert_allclose(total + shared, whole, rtol=1e-4, atol=1e-5)


def _scan_plan_events(run, tmp_path):
    import json

    from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        out = run()
    finally:
        uninstall_telemetry()
        telemetry.close()
    events = [json.loads(line) for line in sink.read_text().splitlines()]
    return out, [e for e in events if e["kind"] == "event" and e["event"] == "mamba2_scan_plan"]


def test_scan_plan_event_on_the_cpu_says_every_m_layer_took_the_jnp_form(tmp_path):
    """Once a traced model, however often it is traced: the two `M` layers of ``MEM*E``, both
    on the `jnp` form, because this is no TPU."""
    model, params = model_and_params(checkpoint_every=1)
    ids = jnp.zeros((1, 32), jnp.int32)

    def run():
        loss = lambda p: jnp.sum(model.apply({"params": p}, ids).logits ** 2)  # noqa: E731
        jax.make_jaxpr(jax.grad(loss))(params)
        jax.make_jaxpr(jax.grad(loss))(params)

    _, (plan,) = _scan_plan_events(run, tmp_path)
    assert (plan["layers"], plan["kernel_layers"], plan["jnp_layers"]) == (2, [], [0, 1])
    assert plan["jnp_reasons"] == ["backend"] and plan["chunk"] == CFG["chunk_size"]
    assert plan["kernel_launches_per_layer_and_pass"] == 0 and plan["kept_bytes_per_layer"] == 0


def test_a_model_told_it_stands_on_a_tpu_runs_the_scan_s_kernel(tmp_path, monkeypatch):
    """Two `M` layers at the published head layout (heads of 64, groups of 8, state 128, chunk
    128), every layer re-computed in the backward pass: with the backend read as a TPU the
    scans go through the Pallas kernels (interpreted here) and the event says 2 of 2; loss and
    gradients are the `jnp` form's, a layer's replay and its backward rule included."""
    from dolomite_engine_tpu.utils import packages

    cfg = dict(
        CFG, n_layer=2, hybrid_override_pattern="MM", n_positions=256,
        mamba_num_heads=16, mamba_head_dim=64, mamba_n_groups=2, ssm_state_size=128, chunk_size=128,
    )
    model = get_model_class("nemotron_h")(config=config_from_dict(cfg), checkpoint_every=1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 1, cfg["vocab_size"])
    seg = jnp.asarray(np.repeat([1, 2, 3], [100, 28, 128]))[None]
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    loss = lambda p: jnp.mean(model.apply({"params": p}, ids, segment_ids=seg).logits ** 2)  # noqa: E731
    reference_value, reference_grads = jax.value_and_grad(loss)(params)

    monkeypatch.setattr(packages, "pallas_interpret_mode", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (value, grads), (plan,) = _scan_plan_events(lambda: jax.value_and_grad(loss)(params), tmp_path)
    assert (plan["layers"], plan["kernel_layers"], plan["jnp_layers"], plan["jnp_reasons"]) == (2, [0, 1], [], [])
    assert plan["kernel_launches_per_layer_and_pass"] == 1 and plan["chunk"] == 128
    assert plan["kept_state_bytes_per_layer"] == 2 * 128 * 16 * 64 * 4  # [1 row, 2 chunks, N, H*P] float32
    np.testing.assert_allclose(value, reference_value, rtol=1e-5)
    for (path, mine), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(reference_grads)):
        np.testing.assert_allclose(mine, ref, rtol=2e-3, atol=2e-5 * float(jnp.abs(ref).max()), err_msg=str(path))


def test_what_the_family_refuses(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    ids = jnp.zeros((1, 16), jnp.int32)
    scanned, _ = model_and_params(scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers with nemotron_h"):
        scanned.init(jax.random.PRNGKey(0), ids)
    model, params = model_and_params()
    with pytest.raises(NotImplementedError, match="no generation cache"):
        model.apply({"params": params}, ids, kv_caches=[None] * 5, cache_index=0)
    with pytest.raises(ValueError, match="pattern"):
        config_from_dict(dict(CFG, hybrid_override_pattern="MEM-E"))
    with pytest.raises(ValueError, match="names 4 layers"):
        config_from_dict(dict(CFG, hybrid_override_pattern="MEM*"))
    with pytest.raises(ValueError, match="experts_held"):
        config_from_dict(dict(CFG, experts_held=[30, 8]))
    MeshManager(tensor_parallel_size=2)
    try:
        with pytest.raises(ValueError, match="tp > 1"):
            model.init(jax.random.PRNGKey(0), ids)
    finally:
        MeshManager.destroy()
