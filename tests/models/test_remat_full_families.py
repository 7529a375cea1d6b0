"""What `full` keeps, a family: a stack whose blocks are applied once a step keeps the attention
kernel's output and log-sum-exp, so its gradient holds one forward kernel a block; the looped
stack applies every block `total_ut_steps` times, keeps nothing, and holds two an application.

The families' small sizes of `family_contract.py`'s table at 128 tokens a row (the kernel's block), the
kernel interpreted: programs and counts, never a time.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.modeling_utils import ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME
from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes, get_model_tflops
from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

from .family_contract import FAMILIES, built
from .test_remat_attention_kernel import count_kernels, through_splash  # noqa: F401 (a fixture)

SEQ, ROWS = 128, 2
# (blocks that attend, applications of each a step)
ATTENDING = {"afmoe": (5, 1), "joyai_llm_flash": (3 + 1, 1), "lfm2_moe": (1, 1), "nemotron_h": (1, 1), "ouro": (2, 4)}


def cfg_of(family: str) -> dict:
    return dict(FAMILIES[family].cfg, n_positions=SEQ)


def loss_and_params(family: str, policy: dict):
    _, _, params, cfg = built(family, n_positions=SEQ)
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="fp32", sequence_length=SEQ, reset_attention_mask=True,
        reset_position_ids=True, zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1, **policy},
    )
    rng = np.random.default_rng(0)
    text = rng.integers(1, cfg["vocab_size"], size=(ROWS, SEQ + 1)).astype(np.int32)
    text[:, [40, 99]] = 0  # three documents a row
    text = jnp.asarray(text)
    return (lambda p: wrapper.loss(p, text, train=True)[0]), params


def launches(family: str, policy: dict) -> tuple[int, int, int]:
    loss, params = loss_and_params(family, policy)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return tuple(count_kernels(jaxpr, f"splash_mha_{kernel}") for kernel in ("fwd", "dkv", "dq"))


@pytest.mark.parametrize("family", FAMILIES)
def test_full_holds_one_forward_kernel_a_block_and_the_looped_stack_two_an_application(family, through_splash):
    blocks, applications = ATTENDING[family]
    each = blocks * applications
    replayed = 2 if applications > 1 else 1
    assert launches(family, {"policy": "full"}) == (replayed * each, each, each)
    assert launches(family, {}) == (replayed * each, each, each)  # no policy given is `full`


@pytest.mark.parametrize("family", FAMILIES)
def test_the_literal_keep_nothing_replays_the_forward_kernel_in_every_family(family, through_splash):
    blocks, applications = ATTENDING[family]
    each = blocks * applications
    assert launches(family, {"checkpoint_policy": "nothing_saveable"}) == (2 * each, each, each)


@pytest.mark.parametrize("family", ["afmoe", "joyai_llm_flash", "ouro"])
def test_remat_plan_says_how_often_full_kept_the_residuals(family, through_splash, tmp_path):
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        loss, params = loss_and_params(family, {"policy": "full"})
        jax.make_jaxpr(jax.grad(loss))(params)
    finally:
        uninstall_telemetry()
        telemetry.close()
    (plan,) = [e for e in map(json.loads, sink.read_text().splitlines()) if e.get("event") == "remat_plan"]
    blocks, applications = ATTENDING[family]
    kept = applications == 1
    assert (plan["attention_kernel_blocks"], plan["attention_kernel_residuals_saved"]) == (blocks, blocks if kept else 0)
    assert plan["saved_names"] == ([ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME] if kept else [])
    assert plan.get("block_applications") == (None if kept else blocks * applications)
    config = config_from_dict(cfg_of(family))
    values = getattr(config, "v_head_dim", config.head_dim)
    assert plan["attention_kernel_residual_bytes_per_block_row"] == config.n_head * SEQ * (values * 4 + 4)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("dtype_bytes", [2, 4])
def test_the_estimate_adds_the_residuals_of_every_attending_block_under_full(family, dtype_bytes):
    """`estimate_remat_activation_bytes`: through the kernel, `full` keeps exactly the output and
    the float32 log-sum-exp of every checkpointed block that attends, beyond the literal
    "keep nothing"; nothing without the kernel, and nothing in the looped stack."""
    config = config_from_dict(cfg_of(family))

    def estimate(policy, attention_kernel):
        return estimate_remat_activation_bytes(
            config, batch_size=ROWS, sequence_length=SEQ, gradient_checkpointing_method="block",
            gradient_checkpointing_args={"checkpoint_every": 1, "policy": policy}, dtype_bytes=dtype_bytes,
            attention_kernel=attention_kernel,
        )

    blocks, applications = ATTENDING[family]
    values = getattr(config, "v_head_dim", config.head_dim)
    residuals = blocks * ROWS * config.n_head * SEQ * (values * dtype_bytes + 4) if applications == 1 else 0
    nothing = estimate("nothing_saveable", True)["activation_bytes_per_replica"]
    assert estimate("full", True)["activation_bytes_per_replica"] - nothing == residuals
    assert estimate("full", False)["activation_bytes_per_replica"] == nothing
    assert estimate("full", True)["delta_vs_full_bytes"] == 0 and estimate("nothing_saveable", True)["delta_vs_full_bytes"] == -residuals


@pytest.mark.parametrize("family", FAMILIES)
def test_the_recompute_term_leaves_out_the_products_the_kept_residuals_stand_for(family):
    """`get_model_tflops` under `full`: where the kernel is expected the replay runs every
    product but the scores' and the values'; the looped stack replays them too."""
    config = config_from_dict(cfg_of(family))
    remat = dict(gradient_checkpointing_method="block", gradient_checkpointing_args={"checkpoint_every": 1, "policy": "full"})
    without = get_model_tflops(config, ROWS, SEQ, **remat)
    through = get_model_tflops(config, ROWS, SEQ, **remat, attention_kernel=True)
    literal = dict(remat, gradient_checkpointing_args={"checkpoint_every": 1, "checkpoint_policy": "nothing_saveable"})
    assert get_model_tflops(config, ROWS, SEQ, **literal, attention_kernel=True) == without
    blocks, applications = ATTENDING[family]
    if applications > 1:
        assert through == without
        return
    if hasattr(config, "attention_product_flops"):
        products = config.attention_product_flops(ROWS, SEQ)
    else:
        products = blocks * 4 * ROWS * SEQ * SEQ * config.n_embd
    assert products > 0 and (without - through) * 1e12 == pytest.approx(products, rel=1e-9)
