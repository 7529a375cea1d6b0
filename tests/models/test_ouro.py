"""`ouro` (models/ouro.py: a looped language model) at a small size on the CPU, against the plain
reference (`benchmark/reference/ouro.py`) on seeded weights. The family's contract — registered,
the loss with its parts (every pass's cross-entropy, the gate's distribution, its entropy) and every
leaf's gradient, three AdamW steps through the trainer's own step, the lowered step — is
`family_contract.py`'s; here is what is the loop's own: what its tree holds; every pass's logits of
packed rows; a shared weight's gradient as the sum of the gradients of four untied copies; the
gate's distribution summing to one and, driven to "never stop early", a loss that is the fourth
pass's cross-entropy; the reference's controls; the loop as ONE scan whatever the number of passes;
what the counts of a step's work read at `total_ut_steps` 1 and 4; the telemetry's ``loop_plan``;
what the family refuses, from one place; an fsdp mesh."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.reference import ouro as reference
from dolomite_engine_tpu.enums import LRDecaySchedule
from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.gpt_dolomite import remat_plan
from dolomite_engine_tpu.models.ouro import OuroStack, exit_distribution, loop_plan, pass_step_counter_names
from dolomite_engine_tpu.ops.rope import RoPEParams, get_cos_sin
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes, get_model_tflops, make_train_step

from .family_contract import FAMILIES, OPTIMIZER, batches, built, contract_tests, model_of, packed_row, program_loss_and_grads, program_tree, text_of, wrapper_for

CFG = FAMILIES["ouro"].cfg
NORMS = FAMILIES["ouro"].norms
globals().update(contract_tests("ouro"))



def test_the_tree_holds_one_set_of_blocks_under_the_loop_s_stack_and_a_gate():
    model, own, (_, _, params, _) = model_of("ouro"), program_tree("ouro"), built("ouro")
    assert model.family_counter_names == pass_step_counter_names(4) == (
        "pass_loss_1", "pass_loss_2", "pass_loss_3", "pass_loss_4", "exit_mass_1", "exit_mass_2", "exit_mass_3", "exit_mass_4",
        "exit_entropy", "weighted_loss", "last_pass_loss",
    )
    # ONE set of blocks under the loop's stack, four norms a block, an untied head, a gate with a bias
    assert set(own) == {"transformer", "lm_head", "exit_gate"} and set(own["transformer"]) == {"wte", "stack"}
    assert set(own["transformer"]["stack"]) == {"h_0", "h_1", "ln_f"} and set(own["transformer"]["stack"]["h_0"]) == {"attn", "mlp", *NORMS}
    assert own["exit_gate"]["kernel"].shape == (32, 1) and own["exit_gate"]["bias"].shape == (1,)
    assert set(own["transformer"]["stack"]["h_0"]["attn"]["c_attn"]) == {"kernel"}  # no bias in the linear layers
    assert {"wte", "lm_head", "gate_w", "gate_b", "ln_f", "layer1.ln_2_out"} <= set(FAMILIES["ouro"].W.leaves_by_name(params))
    config = config_from_dict(CFG)
    assert (config.block_applications, config.head_readings, config.head_dim) == (8, 4, 8)
    assert config.exit_entropy_coef == 0.05 and config.layer_norm_epsilon == 1e-6 and not config.tie_word_embeddings


@pytest.mark.parametrize("docs", [(23, 41), (10, 37, 17)], ids=["two_documents", "three_documents"])
def test_every_pass_s_logits_of_a_packed_row_follow_the_reference(docs):
    model, weights, params, _ = built("ouro")
    text = packed_row(docs)
    batch = wrapper_for(CFG).prepare_inputs_and_labels(jnp.asarray(text)[None])
    with jax.default_matmul_precision("highest"):
        passes = model.apply(
            {"params": params}, batch["input_ids"], position_ids=batch["position_ids"], segment_ids=batch["segment_ids"],
            method=lambda m, *args, **kwargs: m.transformer(*args, **kwargs),
        )
        ref = reference.forward_logits(CFG, weights, jnp.asarray(text[:-1]))
        assert passes.shape == (1, 4, 64, 32) and len(ref) == 4
        for t in range(4):
            np.testing.assert_allclose(jnp.dot(passes[0, t], params["lm_head"]["kernel"].T), ref[t], rtol=2e-4, atol=2e-4)
        assert float(jnp.abs(ref[0] - ref[3]).max()) > 0.1  # the passes differ: the loop does something
        # without a loss the model hands out the LAST pass's logits (early_exit_threshold 1)
        logits = model.apply({"params": params}, batch["input_ids"], position_ids=batch["position_ids"], segment_ids=batch["segment_ids"]).logits
        np.testing.assert_allclose(logits[0], ref[3], rtol=2e-4, atol=2e-4)
        # and the documents do not see each other: a document alone gives its part of the row
        first = model.apply({"params": params}, batch["input_ids"][:, : docs[0]]).logits
        np.testing.assert_allclose(logits[:, : docs[0]], first, rtol=2e-4, atol=2e-4)


def two_rows():
    return text_of(FAMILIES["ouro"].gradient_rows["chunked_head"])


def test_the_loss_is_its_parts():
    """The weighted cross-entropy less beta x the entropy, plus a z-loss of some 1e-4 x lse^2."""
    (loss, counters), _ = program_loss_and_grads("ouro", "chunked_head")
    assert 0 < float(loss - (counters["weighted_loss"] - 0.05 * counters["exit_entropy"])) < 1e-2


def test_a_shared_weight_s_gradient_is_the_sum_over_four_untied_copies():
    """The program's own stack applied four times in a Python loop over FOUR copies of its
    parameters (no scan, no sharing), the loss through the same head and gate: the gradient of the
    scanned model's one stack is the sum of the four copies' gradients, leaf by leaf."""
    model, params = model_of("ouro", checkpoint_every=1), built("ouro")[2]
    config = config_from_dict(CFG)
    batch = wrapper_for(CFG).prepare_inputs_and_labels(two_rows())
    ids, positions, segments, labels = batch["input_ids"], batch["position_ids"], batch["segment_ids"], batch["labels"]
    rope = get_cos_sin(RoPEParams.from_config(config.head_dim, config.rope_theta, None, config.n_positions), positions, dtype=jnp.float32)
    stack = OuroStack(config=config)

    def untied_loss(copies, rest):
        hidden, passes = rest["transformer"]["wte"]["embedding"][ids], []
        for copy in copies:
            hidden, _ = stack.apply({"params": copy}, hidden, None, segments, rope, True)
            passes.append(hidden)
        with_stack = dict(rest, transformer=dict(rest["transformer"], stack=copies[0]))
        return model.apply({"params": with_stack}, jnp.stack(passes, axis=1), labels, method="gated_loss")[0]

    def tied_loss(p):
        return model.apply({"params": p}, ids, position_ids=positions, segment_ids=segments, labels=labels).loss

    rest = dict(params, transformer={"wte": params["transformer"]["wte"]})
    with jax.default_matmul_precision("highest"):
        tied_value, tied = jax.value_and_grad(tied_loss)(params)
        untied_value, (per_copy, rest_grads) = jax.value_and_grad(untied_loss, argnums=(0, 1))([params["transformer"]["stack"]] * 4, rest)
    np.testing.assert_allclose(tied_value, untied_value, rtol=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *per_copy)
    for (path, mine), theirs in zip(jax.tree_util.tree_leaves_with_path(tied["transformer"]["stack"]), jax.tree.leaves(summed)):
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-6 * float(jnp.abs(theirs).max()) + 1e-9, err_msg=str(path))
    # ... and no single copy's gradient is the whole (every pass contributes)
    first = jax.tree.leaves(per_copy[0])[0]
    assert float(jnp.abs(first - jax.tree.leaves(summed)[0]).max()) > 1e-3 * float(jnp.abs(first).max())
    np.testing.assert_allclose(tied["lm_head"]["kernel"], rest_grads["lm_head"]["kernel"], rtol=1e-4, atol=1e-8)


def test_the_exit_distribution_sums_to_one_and_a_gate_that_never_stops_leaves_the_fourth_pass():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 3, 7)) * 3, jnp.float32)  # [passes, rows, tokens]
    log_p, p = (jnp.swapaxes(x, 0, 1) for x in exit_distribution(jnp.swapaxes(logits, 0, 1)))  # (the model's layout is [rows, passes, tokens])
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    stop = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[0], stop[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], stop[2] * (1 - stop[0]) * (1 - stop[1]), rtol=1e-5)
    np.testing.assert_allclose(p[3], (1 - stop[0]) * (1 - stop[1]) * (1 - stop[2]), rtol=1e-5)  # the last takes what is left: its own gate unread
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    # a gate driven far to either side gives no NaN, forward or backward
    for value in (-200.0, 200.0):
        grads = jax.grad(lambda g: jnp.sum(exit_distribution(g)[1] * exit_distribution(g)[0]))(jnp.full((2, 4, 2), value))
        assert bool(jnp.all(jnp.isfinite(grads)))
    # the model with the gate's bias far negative: nothing stops early, the loss is the fourth pass's cross-entropy (+ z)
    params = built("ouro")[2]
    never = dict(params, exit_gate={"kernel": jnp.zeros_like(params["exit_gate"]["kernel"]), "bias": jnp.asarray([-60.0])})
    wrapper = wrapper_for(dict(CFG, z_loss_coef=0.0))
    with jax.default_matmul_precision("highest"):
        loss, counters = wrapper.loss(never, two_rows(), train=True)
    np.testing.assert_allclose(loss, counters["pass_loss_4"], rtol=1e-6)
    assert float(counters["exit_mass_4"]) == pytest.approx(1.0, abs=1e-6) and float(counters["exit_entropy"]) < 1e-6
    assert abs(float(counters["pass_loss_4"]) - float(counters["pass_loss_1"])) > 1e-3


def test_the_reference_s_controls_are_other_programs():
    """Three passes and an unweighted loss both leave the limits the trainer's step is held to
    (`family_contract`'s three steps against the reference's)."""
    data = batches()[:1]
    ref = reference.train_steps(CFG, 11, data, OPTIMIZER)
    three = reference.train_steps(CFG, 11, data, OPTIMIZER, passes=3)
    assert len(three["pass_losses"][0]) == 3 and compare.worst_leaf_gap(three["grad_norms"], ref["grad_norms"])[0] > 0.05
    plain = reference.train_steps(CFG, 11, data, OPTIMIZER, weigh=False)
    np.testing.assert_allclose(plain["losses"][0], np.mean(plain["pass_losses"][0]), rtol=1e-3)  # (+ the z-loss)
    assert plain["grad_norms"]["gate_w"] == 0.0 == plain["grad_norms"]["gate_b"] and ref["grad_norms"]["gate_w"] > 0


@pytest.mark.parametrize("passes", [1, 4])
def test_the_loop_is_one_scan_whatever_the_number_of_passes(passes):
    """The jaxpr of loss and gradient holds ONE forward scan over `total_ut_steps` iterations (and
    its transpose) whose body holds each block's attention once: the count of dot_generals does not
    grow with the passes."""
    cfg = dict(CFG, total_ut_steps=passes)
    params = built("ouro")[2]
    text = two_rows()
    wrapper = wrapper_for(cfg, gradient_checkpointing_args={"checkpoint_every": 1})
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: wrapper.loss(p, text, train=True)[0]))(params))
    assert jaxpr.count(f"length={passes}\n") + jaxpr.count(f"length={passes} ") >= 2  # the scan and its transpose
    if passes == 4:
        one = str(jax.make_jaxpr(jax.grad(lambda p: wrapper_for(dict(CFG, total_ut_steps=1), gradient_checkpointing_args={"checkpoint_every": 1}).loss(p, text, train=True)[0]))(params))
        assert jaxpr.count("dot_general") == one.count("dot_general")


@pytest.mark.parametrize("passes", [1, 4])
def test_a_step_s_work_is_counted_by_block_applications_and_head_readings(passes):
    """`get_model_tflops` and `estimate_remat_activation_bytes` take passes x blocks and the head's
    readings from the config; at one pass they read what the dense family of the same widths reads."""
    cfg = dict(CFG, total_ut_steps=passes)
    looped = config_from_dict(cfg)
    dense = config_from_dict(dict(
        cfg, model_type="gpt_dolomite", attention_head_type="mha", position_embedding_type="rope", activation_function="swiglu",
        normalization_function="rmsnorm", add_bias=False,
    ))
    assert (looped.block_applications, looped.head_readings) == (passes * 2, passes)
    b, s = 2, 64
    remat = dict(gradient_checkpointing_method="block", gradient_checkpointing_args={"checkpoint_every": 1, "policy": "full"})
    for kwargs in ({}, remat):
        once = get_model_tflops(dense, b, s, **kwargs)
        assert get_model_tflops(looped, b, s, **kwargs) == pytest.approx(passes * once)  # blocks, their replay and the head alike
    kept = estimate_remat_activation_bytes(looped, b, s, dtype_bytes=2, **remat)
    dense_kept = estimate_remat_activation_bytes(dense, b, s, dtype_bytes=2, **remat)
    assert kept["activation_bytes_per_replica"] == passes * dense_kept["activation_bytes_per_replica"] == passes * 2 * b * s * 32 * 2
    assert remat_plan("full", 1, [True, True], [0, 0], passes) .get("block_applications") == (8 if passes == 4 else None)
    plan = loop_plan(looped, (True, True), b, s, 2)
    assert (plan["passes"], plan["blocks"], plan["block_applications"], plan["applications_rematerialized"], plan["head_readings"]) == (passes, 2, 2 * passes, 2 * passes, passes)
    assert plan["kept_input_bytes"] == passes * 3 * b * s * 32 * 2  # a block's input an application, and every pass's output


def test_loop_plan_and_remat_plan_events_are_written_once(tmp_path):
    from dolomite_engine_tpu.utils.telemetry import KNOWN_EVENTS, Telemetry, install_telemetry, uninstall_telemetry

    assert "loop_plan" in KNOWN_EVENTS
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        params = built("ouro")[2]
        wrapper = wrapper_for(CFG, gradient_checkpointing_args={"checkpoint_every": 1})
        for _ in range(2):  # traced again: nothing new to say
            jax.make_jaxpr(jax.grad(lambda p: wrapper.loss(p, two_rows(), train=True)[0]))(params)
    finally:
        uninstall_telemetry()
        telemetry.close()
    events = [json.loads(line) for line in sink.read_text().splitlines()]
    (loop,) = [e for e in events if e.get("event") == "loop_plan"]
    assert (loop["passes"], loop["blocks"], loop["block_applications"], loop["rows"], loop["tokens_per_row"]) == (4, 2, 8, 2, 64)
    assert loop["kept_input_bytes"] == 4 * 3 * 2 * 64 * 32 * 4
    (remat,) = [e for e in events if e.get("event") == "remat_plan"]
    assert (remat["blocks"], remat["blocks_rematerialized"], remat["block_applications"], remat["applications_rematerialized"]) == (2, 2, 8, 8)


def test_what_the_family_refuses_from_one_place(eight_devices):
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    ids = jnp.zeros((1, 16), jnp.int32)
    message = "is not built; the training path on dp / fsdp meshes only"
    with pytest.raises(NotImplementedError, match="ouro: scan_layers .*" + message):
        model_of("ouro", scan_layers=True).init(jax.random.PRNGKey(0), ids)
    model, _, params, _ = built("ouro")
    with pytest.raises(NotImplementedError, match="ouro: a KV cache .* per pass and layer.*" + message):
        model.apply({"params": params}, ids, kv_caches=[None] * 2, cache_index=0)
    with pytest.raises(NotImplementedError, match="ouro: a KV cache .*" + message):
        model.init_kv_caches(1, 16)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        config_from_dict(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="total_ut_steps"):
        config_from_dict(dict(CFG, total_ut_steps=0))
    with pytest.raises(ValueError, match="position_embedding_type"):
        config_from_dict(dict(CFG, position_embedding_type="alibi"))
    MeshManager(tensor_parallel_size=2)
    try:
        with pytest.raises(NotImplementedError, match="ouro: a mesh with tp > 1 .*" + message):
            model.init(jax.random.PRNGKey(0), ids)
    finally:
        MeshManager.destroy()


def test_the_family_trains_on_an_fsdp_mesh_to_the_single_device_loss(eight_devices, capfd):
    """ZeRO-3 over eight virtual devices (the parameters and the rows sharded): the first step's
    loss is the single-device loss of the same eight rows, the steps after it go down, and the
    partitioner replicates nothing it was not asked to. fsdp was expected to work; here it is run."""
    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding

    rng = np.random.default_rng(5)
    text = rng.integers(1, CFG["vocab_size"], size=(8, CFG["n_positions"] + 1)).astype(np.int32)
    text[:, 30] = 0
    params = built("ouro")[2]
    alone = float(wrapper_for(CFG).loss(params, jnp.asarray(text), train=True)[0])
    MeshManager()
    mesh = MeshManager.get_mesh()
    try:
        wrapper = wrapper_for(CFG, zero_stage=3, gradient_checkpointing_args={"checkpoint_every": 1})
        schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=1e-3)
        optimizer = get_optimizer("TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, schedule)
        state, _ = create_sharded_train_state(wrapper, optimizer, mesh, jax.random.PRNGKey(0))
        state = state.replace(params=jax.tree.map(lambda mine, theirs: jax.device_put(mine, theirs.sharding), params, state.params))
        step = jax.jit(make_train_step(lambda p, micro, rng: wrapper.loss(p, micro["text"], train=True), optimizer, has_aux=True), donate_argnums=0)
        with mesh:
            batch = {"text": jax.device_put(jnp.asarray(text)[None], named_sharding(None, ("dp", "fsdp")))}
            losses = []
            for i in range(3):
                state, metrics = step(state, batch, jax.random.PRNGKey(i))
                losses.append(float(metrics["loss"]))
    finally:
        MeshManager.destroy()
    assert losses[0] == pytest.approx(alone, rel=1e-4) and losses[-1] < losses[0], (alone, losses)
    assert "Involuntary full rematerialization" not in capfd.readouterr().err
