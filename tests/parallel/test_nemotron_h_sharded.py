"""`nemotron_h` on the virtual 8-device CPU mesh: the new parameters carry logical axes that
ZeRO-3 shards (the hidden axis of every projection and bank over fsdp), the train step
compiles and runs under fsdp 8 and follows the one-device step, and the layouts the family
does not build raise instead of replicating silently."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.distributed import create_sharded_train_state
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
from dolomite_engine_tpu.train_utils import make_train_step

from ..models.family_contract import FAMILIES, batches

CFG = FAMILIES["nemotron_h"].cfg


def wrapper():
    return ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=CFG, dtype="fp32", sequence_length=CFG["n_positions"],
        reset_attention_mask=True, zero_stage=3, gradient_checkpointing_args={"checkpoint_every": 1},
    )


def optimizer_for(model):
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=1e-3)
    return get_optimizer("TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, schedule, model_config=model.config)


def run_steps(mesh, rows):
    model = wrapper()
    optimizer = optimizer_for(model)
    state, _ = create_sharded_train_state(model, optimizer, mesh, jax.random.PRNGKey(0))
    step = jax.jit(
        make_train_step(lambda p, micro, rng: model.loss(p, micro["text"], rngs=None, train=True), optimizer, has_aux=True),
        donate_argnums=(0,),
    )
    losses, counters = [], []
    with mesh:
        for text in batches(steps=2, rows=rows, seed=4):
            batch = {"text": jax.device_put(jnp.asarray(text)[None], named_sharding(None, ("dp", "fsdp", "ep")))}
            state, metrics = step(state, batch, jax.random.PRNGKey(1))
            losses.append(float(metrics["loss"]))
            counters.append(np.asarray(metrics["counters"]["routed_slots"]))
    return state, losses, counters


def test_train_step_under_fsdp8_follows_one_device(eight_devices):
    MeshManager(data_parallel_sharding_world_size=8)
    try:
        state, losses, counters = run_steps(MeshManager.get_mesh(), rows=8)
        specs = {
            "in_proj": state.params["transformer"]["h_0"]["mixer"]["in_proj"]["kernel"].sharding.spec,
            "c_fc": state.params["transformer"]["h_1"]["moe"]["c_fc"]["kernel"].sharding.spec,
            "lm_head": state.params["lm_head"]["kernel"].sharding.spec,
        }
    finally:
        MeshManager.destroy()
    assert "fsdp" in str(specs["in_proj"]) and "fsdp" in str(specs["c_fc"]) and "fsdp" in str(specs["lm_head"]), specs
    MeshManager(devices=jax.devices()[:1])
    try:
        _, alone, counters_alone = run_steps(MeshManager.get_mesh(), rows=8)
    finally:
        MeshManager.destroy()
    np.testing.assert_allclose(losses, alone, rtol=1e-4)
    # a top-k near-tie may fall the other way under another reduction order: a slot or two
    np.testing.assert_allclose(counters, counters_alone, atol=3)


@pytest.mark.parametrize("axis", ["tp", "ep"])
def test_tp_and_ep_raise_instead_of_replicating(eight_devices, axis):
    kwargs = {"tensor_parallel_size": 2} if axis == "tp" else {"expert_parallel_size": 2}
    MeshManager(**kwargs)
    try:
        with pytest.raises(ValueError, match=f"{axis} > 1"):
            wrapper().abstract_params()
    finally:
        MeshManager.destroy()
