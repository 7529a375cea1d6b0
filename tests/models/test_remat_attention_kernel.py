"""`full` and `save_dots` keep the attention kernel's output and log-sum-exp across the remat boundary.

A `pallas_call` is no dot: under `dots_saveable` alone (and under the literal
`nothing_saveable`) the backward pass of a remat'ed block runs the whole forward kernel again.
The kernel's residuals carry a `checkpoint_name` (`ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME`)
and the `full` / `save_dots` / `offload_dots` policies keep that name (`full` only in a stack
that applies its blocks once a step). Here on the CPU the kernel runs interpreted, so these tests see programs
(which kernels a gradient holds, what a policy saves) and values, never a time.
"""

import collections
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel

from dolomite_engine_tpu.models import config_from_dict, modeling_utils
from dolomite_engine_tpu.models.gpt_dolomite import (
    GPTDolomiteForCausalLM,
    names_kept_on_device,
    remat_plan,
    resolve_remat_policy,
)
from dolomite_engine_tpu.models.modeling_utils import (
    ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME,
    ATTENTION_OUT_CHECKPOINT_NAME,
)
from dolomite_engine_tpu.ops.attention import _tpu_splash_attention
from dolomite_engine_tpu.parallel.sharding import get_logical_axis_rules
from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes
from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

N_LAYER, N_HEAD, HEAD, SEQ, BATCH = 4, 2, 128, 128, 4


@pytest.fixture()
def through_splash(monkeypatch):
    """Every block's attention through the splash kernel, interpreted, under its window (the
    dispatch in `ops.attention.attention` takes the kernel on a TPU only)."""

    def attend(q, k, v, *, softmax_scale, segment_ids=None, window=None, **_):
        return _tpu_splash_attention(q, k, v, segment_ids, softmax_scale, interpret=True, window=window)

    monkeypatch.setattr(modeling_utils, "attention_op", attend)


@pytest.fixture()
def kernel_without_the_name(monkeypatch):
    """The kernel as it was built before its residuals had a name."""
    make = splash_attention_kernel.make_splash_mha_single_device

    def without(mask, **kwargs):
        assert kwargs.pop("residual_checkpoint_name") == ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME
        return make(mask, **kwargs)

    monkeypatch.setattr(splash_attention_kernel, "make_splash_mha_single_device", without)


def _model(policy, scan_layers=True, checkpoint_every=2):
    config = config_from_dict(
        dict(
            model_type="gpt_dolomite", vocab_size=256, n_positions=SEQ, n_embd=N_HEAD * HEAD, n_layer=N_LAYER,
            n_head=N_HEAD, num_key_value_heads=N_HEAD, attention_head_type="mha", position_embedding_type="rope",
            activation_function="swiglu", normalization_function="rmsnorm", add_bias=False, n_inner=256,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=1, pad_token_id=2,
        )
    )
    return GPTDolomiteForCausalLM(
        config=config, checkpoint_every=checkpoint_every, checkpoint_policy=policy, scan_layers=scan_layers
    )


def _loss_and_params(model):
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, size=(BATCH, SEQ)), jnp.int32)
    segments = jnp.asarray(np.r_[np.full(48, 1), np.full(80, 2)][None].repeat(BATCH, 0), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)

    def loss(p):
        return model.apply(p, ids, segment_ids=segments, labels=ids, compute_loss=True).loss

    return loss, params


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def count_kernels(jaxpr, needle: str, times: int = 1) -> int:
    """Launches of the Pallas kernels named like `needle` that one evaluation of `jaxpr`
    makes: a scan's body counts `length` times."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += times * (needle in eqn.params["name"])
            continue
        inner_times = times * (eqn.params["length"] if eqn.primitive.name == "scan" else 1)
        total += sum(count_kernels(sub, needle, inner_times) for sub in _sub_jaxprs(eqn))
    return total


@pytest.mark.parametrize("mesh", [None, "mesh_2x2x2"], ids=["no_mesh", "mesh"])
@pytest.mark.parametrize(
    "policy, forwards",
    [
        ("save_dots", N_LAYER),
        ("offload_dots", N_LAYER),
        ("full", N_LAYER),
        (None, N_LAYER),  # no policy given is `full`
        ("save_attention_out", 2 * N_LAYER),
        ("dots_saveable", 2 * N_LAYER),  # the raw jax name stays raw: it keeps no name
        ("nothing_saveable", 2 * N_LAYER),  # the literal "keep nothing"
    ],
)
def test_gradient_holds_the_forward_kernel_once_a_layer_under_save_dots(policy, forwards, mesh, request, through_splash):
    """The gradient of a scanned, every-2 remat'ed model: `n_layer` forward kernels where the
    policy keeps the kernel's residuals, `2 x n_layer` where the backward pass replays them;
    the same inside the `shard_map` a mesh puts the kernel in."""
    scope = contextlib.ExitStack()
    if mesh:
        scope.enter_context(request.getfixturevalue(mesh))
        scope.enter_context(nn.logical_axis_rules(get_logical_axis_rules(stage=3)))
    with scope:
        loss, params = _loss_and_params(_model(policy))
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert ("shard_map" in str(jaxpr)) == bool(mesh)
    assert count_kernels(jaxpr.jaxpr, "splash_mha_fwd") == forwards
    assert count_kernels(jaxpr.jaxpr, "splash_mha_dkv") == N_LAYER
    assert count_kernels(jaxpr.jaxpr, "splash_mha_dq") == N_LAYER


def test_unrolled_every_2_replays_only_the_rematerialized_blocks(through_splash):
    """Unrolled, every second block sits under `jax.checkpoint`: those two replay the kernel
    under `nothing_saveable` and do not under `save_dots` and `full`."""
    for policy, forwards in (("save_dots", N_LAYER), ("full", N_LAYER), ("nothing_saveable", N_LAYER + N_LAYER // 2)):
        loss, params = _loss_and_params(_model(policy, scan_layers=False))
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
        assert count_kernels(jaxpr, "splash_mha_fwd") == forwards
        assert (count_kernels(jaxpr, "splash_mha_dkv"), count_kernels(jaxpr, "splash_mha_dq")) == (N_LAYER, N_LAYER)


@pytest.mark.parametrize("policy", ["save_dots", "full"])
def test_gradients_are_bit_for_bit_those_of_the_kernel_without_the_name(policy, through_splash, request):
    """What is kept is what the replay would compute again."""
    loss, params = _loss_and_params(_model(policy))
    kept = jax.jit(jax.value_and_grad(loss))(params)
    request.getfixturevalue("kernel_without_the_name")
    replayed = jax.jit(jax.value_and_grad(loss))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(replayed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "policy, names",
    [
        ("full", (ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME,)),
        ("nothing_saveable", ()),
        ("save_attention_out", (ATTENTION_OUT_CHECKPOINT_NAME,)),
    ],
)
def test_policies_without_the_name_save_what_they_saved(policy, names, through_splash, request, capsys):
    """`nothing_saveable` and `save_attention_out` keep their saved sets exactly: the residuals
    of the loss are the same list with the kernel's name and without it. `full` keeps that list
    and, of each rematerialized block, the kernel's output and log-sum-exp."""
    assert names_kept_on_device(resolve_remat_policy(policy)) == names

    def saved():
        loss, params = _loss_and_params(_model(policy, scan_layers=False))
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(loss, params)
        return [line.split(" from ")[0] for line in capsys.readouterr().out.splitlines()]

    with_name = saved()
    request.getfixturevalue("kernel_without_the_name")
    without = saved()
    assert len(without) > N_LAYER
    if ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME not in names:
        assert with_name == without
        return
    assert not collections.Counter(without) - collections.Counter(with_name)
    kept = collections.Counter(line.split(" ")[0] for line in (collections.Counter(with_name) - collections.Counter(without)).elements())
    rows = BATCH * SEQ  # laid end to end
    assert kept == {f"f32[{N_HEAD},{rows},{HEAD}]": N_LAYER // 2, f"f32[{N_HEAD},{rows}]": N_LAYER // 2}


def test_names_the_policies_keep():
    kernel = ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME
    assert names_kept_on_device(resolve_remat_policy("save_dots")) == (kernel,)
    assert names_kept_on_device(resolve_remat_policy("offload_dots")) == (kernel,)
    assert names_kept_on_device(resolve_remat_policy("dots_saveable")) == ()
    assert names_kept_on_device(resolve_remat_policy("everything_saveable")) == (ATTENTION_OUT_CHECKPOINT_NAME, kernel)
    assert names_kept_on_device(resolve_remat_policy("full")) == (kernel,)
    assert names_kept_on_device(resolve_remat_policy(None)) == (kernel,)
    assert names_kept_on_device(resolve_remat_policy("nothing_saveable")) == ()
    # a stack that applies its blocks more than once a step keeps nothing under `full`
    assert names_kept_on_device(resolve_remat_policy("full", applications_per_block=4)) == ()
    assert names_kept_on_device(resolve_remat_policy(None, applications_per_block=4)) == ()
    assert names_kept_on_device(resolve_remat_policy("save_dots", applications_per_block=4)) == (kernel,)
    # the offloaded dots stay offloaded, what is no dot and has no name is recomputed
    offload = resolve_remat_policy("offload_dots")
    dot = jax.make_jaxpr(jnp.dot)(jnp.ones((2, 2)), jnp.ones((2, 2))).eqns[0]
    assert isinstance(offload(dot.primitive, **dot.params), jax.ad_checkpoint.Offloadable)
    assert resolve_remat_policy("save_dots")(dot.primitive, **dot.params) is True


@pytest.mark.parametrize(
    "policy, scan_layers, kernel, expected",
    [
        # (blocks under jax.checkpoint, of them through the kernel, of them with residuals kept)
        ("save_dots", True, True, (4, 4, 4)),
        ("offload_dots", True, True, (4, 4, 4)),
        ("full", True, True, (4, 4, 4)),
        ("dots_saveable", True, True, (4, 4, 0)),
        ("nothing_saveable", True, True, (4, 4, 0)),
        ("save_dots", False, True, (2, 2, 2)),
        ("full", False, True, (2, 2, 2)),
        ("save_dots", True, False, (4, 0, 0)),  # sdpa: the attention's products are dots
        ("full", True, False, (4, 0, 0)),  # sdpa: nothing carries the name
    ],
)
def test_remat_plan_event_is_written_once_with_the_counts(policy, scan_layers, kernel, expected, tmp_path, request):
    if kernel:
        request.getfixturevalue("through_splash")
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        loss, params = _loss_and_params(_model(policy, scan_layers=scan_layers))
        jax.make_jaxpr(jax.grad(loss))(params)
        jax.make_jaxpr(jax.grad(loss))(params)  # traced again: nothing new to say
    finally:
        uninstall_telemetry()
        telemetry.close()
    events = [json.loads(line) for line in sink.read_text().splitlines()]
    (plan,) = [e for e in events if e["kind"] == "event" and e["event"] == "remat_plan"]
    counts = (plan["blocks_rematerialized"], plan["attention_kernel_blocks"], plan["attention_kernel_residuals_saved"])
    assert counts == expected and plan["blocks"] == N_LAYER
    assert (plan["policy"], plan["checkpoint_every"]) == (policy, 2)
    assert plan["saved_names"] == list(names_kept_on_device(resolve_remat_policy(policy)))
    # float32 here: the output [heads, S, head] and the log-sum-exp [heads, S] of one batch row
    row_bytes = N_HEAD * SEQ * (HEAD * 4 + 4) if kernel else 0
    assert plan["attention_kernel_residual_bytes_per_block_row"] == row_bytes


def test_remat_plan_counts_blocks_not_kernel_calls():
    plan = remat_plan("save_dots", 2, [True, False, True, False], [100, 100, 0, 100])
    assert (plan["blocks_rematerialized"], plan["attention_kernel_blocks"], plan["attention_kernel_residuals_saved"]) == (2, 1, 1)
    assert remat_plan("full", 1, [True], [100])["attention_kernel_residuals_saved"] == 1
    assert remat_plan(None, 1, [True], [100])["saved_names"] == (ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME,)
    assert remat_plan("nothing_saveable", 1, [True], [100])["attention_kernel_residuals_saved"] == 0
    # a looped stack: every application of a block would keep them, so `full` keeps none
    looped = remat_plan("full", 1, [True, True], [100, 100], applications_per_block=4)
    assert (looped["attention_kernel_blocks"], looped["attention_kernel_residuals_saved"], looped["saved_names"]) == (2, 0, ())
    assert (looped["block_applications"], looped["applications_rematerialized"]) == (8, 8)


@pytest.mark.parametrize("dtype_bytes", [2, 4])
def test_estimate_counts_the_kernels_residuals_for_save_dots_alone(dtype_bytes):
    """The `model_report` remat line: through the kernel `save_dots` keeps, beyond what the raw
    `dots_saveable` keeps, exactly the output and the float32 log-sum-exp of every
    checkpointed block; `full` keeps them beyond the literal `nothing_saveable`, and nothing
    without the kernel; `offload_dots` keeps them on the device."""
    config = _model("full").config
    batch, every = 3, 2

    def estimate(policy, attention_kernel):
        return estimate_remat_activation_bytes(
            config, batch_size=batch, sequence_length=SEQ, gradient_checkpointing_method="block",
            gradient_checkpointing_args={"checkpoint_every": every, "policy": policy}, dtype_bytes=dtype_bytes,
            attention_kernel=attention_kernel,
        )

    residuals = (N_LAYER // every) * batch * (N_HEAD * SEQ * HEAD * dtype_bytes + N_HEAD * SEQ * 4)
    raw = estimate("dots_saveable", True)["activation_bytes_per_replica"]
    assert estimate("save_dots", True)["activation_bytes_per_replica"] - raw == residuals
    nothing = estimate("nothing_saveable", True)["activation_bytes_per_replica"]
    assert estimate("full", True)["activation_bytes_per_replica"] - nothing == residuals
    assert estimate("full", False)["activation_bytes_per_replica"] == nothing
    assert estimate("nothing_saveable", True)["activation_bytes_per_replica"] == estimate("nothing_saveable", False)["activation_bytes_per_replica"]
    # the delta is against what `full` keeps where the run is: 0 for `full` itself either way
    assert estimate("full", True)["delta_vs_full_bytes"] == estimate("full", False)["delta_vs_full_bytes"] == 0
    assert estimate("nothing_saveable", True)["delta_vs_full_bytes"] == -residuals
    assert estimate("save_attention_out", True)["activation_bytes_per_replica"] == estimate("save_attention_out", False)["activation_bytes_per_replica"]
    offload = estimate("offload_dots", True)
    assert offload["activation_bytes_per_replica"] - nothing == residuals
    assert offload["host_offload_bytes_per_replica"] == raw - nothing
    # on the XLA path the scores and the context are dots, and were always counted
    assert estimate("save_dots", False)["activation_bytes_per_replica"] > estimate("save_dots", True)["activation_bytes_per_replica"]
