"""cpu_offload (ZeRO-Offload equivalent): optimizer state parked in pinned_host memory.

Parity: reference accepts DeepSpeed `cpu_offload` (arguments.py:338) and delegates to
ZeRO-Offload. Here the same YAML flag places the optax state in the host memory space via
sharding memory_kind; the train step streams it to device for the update (TPU-only — CPU XLA
has no `annotate_device_placement` for host transfers inside jit, so the flag warn-and-ignores
off-TPU, `train_utils.resolve_cpu_offload`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.distributed import create_sharded_train_state
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
from dolomite_engine_tpu.train_utils import make_train_step, offload_jit_kwargs, resolve_cpu_offload


def _wrapper():
    return ModelWrapperForPretraining(
        mode=Mode.training,
        pretrained_config=dict(
            model_type="gpt_dolomite", vocab_size=256, n_positions=64, n_embd=64,
            n_layer=2, n_head=4, attention_head_type="mha", position_embedding_type="rope",
            activation_function="swiglu", normalization_function="rmsnorm",
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
            bos_token_id=0, eos_token_id=1, pad_token_id=2,
        ),
        dtype="fp32",
        sequence_length=32,
        zero_stage=3,
    )


def _optimizer():
    sched = get_scheduler(2, 0, None, 50, LRDecaySchedule.cosine, 0.1, base_lr=1e-3)
    return get_optimizer(
        "TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, sched
    )


def test_offloaded_state_parks_on_pinned_host(eight_devices):
    """State creation with offload: opt-state leaves live in pinned_host, params on device,
    ZeRO sharding layout (specs) unchanged, values identical to the device-resident init."""
    MeshManager.destroy()
    MeshManager(data_parallel_sharding_world_size=8, data_parallel_replication_world_size=1)
    mesh = MeshManager.get_mesh()

    wrapper = _wrapper()
    opt = _optimizer()
    base, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(0))
    off, _ = create_sharded_train_state(
        wrapper, opt, mesh, jax.random.PRNGKey(0), offload_optimizer=True
    )

    kinds = {
        leaf.sharding.memory_kind
        for leaf in jax.tree.leaves(off.opt_state)
        if hasattr(leaf, "sharding")
    }
    assert "pinned_host" in kinds and "device" not in kinds, kinds
    pkinds = {leaf.sharding.memory_kind for leaf in jax.tree.leaves(off.params)}
    assert pkinds == {"device"}, pkinds

    # identical values and identical partition specs — only the memory space moved
    for a, b in zip(jax.tree.leaves(base.opt_state), jax.tree.leaves(off.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if hasattr(a, "sharding") and hasattr(a.sharding, "spec"):
            assert a.sharding.spec == b.sharding.spec
    MeshManager.destroy()


def test_cpu_offload_flag_warns_and_ignores_off_tpu():
    from dolomite_engine_tpu.arguments import TrainingArgs

    args = TrainingArgs(
        model_args=dict(
            model_class="AutoModelForCausalLM",
            pretrained_config=dict(model_type="gpt_dolomite", n_layer=1, n_embd=32,
                                   n_head=2, vocab_size=64, n_positions=32),
        ),
        tuning_args=dict(tuning_method="pretraining"),
        training_parameters=dict(num_training_steps=1, micro_batch_size=1,
                                 eval_during_training=False),
        datasets=[dict(class_name="DebugDataset", data_name="debug",
                       class_args=dict(num_examples=4))],
        save_args=dict(save_path="/tmp/x", save_interval=1),
        random_args=dict(seed=1),
        distributed_args=dict(cpu_offload=True),
    )
    assert jax.default_backend() != "tpu"  # conftest pins tests to CPU
    assert resolve_cpu_offload(args) is False


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="in-jit host streaming is TPU-only")
def test_offloaded_training_matches_device_training(eight_devices):
    MeshManager.destroy()
    MeshManager(data_parallel_sharding_world_size=8, data_parallel_replication_world_size=1)
    mesh = MeshManager.get_mesh()
    tokens = np.random.RandomState(0).randint(0, 256, size=(1, 8, 33)).astype(np.int32)

    losses = {}
    for offload in (False, True):
        wrapper = _wrapper()
        opt = _optimizer()
        state, _ = create_sharded_train_state(
            wrapper, opt, mesh, jax.random.PRNGKey(0), offload_optimizer=offload
        )

        def loss_fn(params, micro, rng):
            return wrapper.loss(params, micro["text"], train=True)

        kwargs = offload_jit_kwargs(state) if offload else {}
        step_fn = jax.jit(
            make_train_step(loss_fn, opt, offload_optimizer=offload),
            donate_argnums=0,
            **kwargs,
        )
        run = []
        with mesh:
            batch = {
                "text": jax.device_put(jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp")))
            }
            for i in range(3):
                state, metrics = step_fn(state, batch, jax.random.PRNGKey(i))
                run.append(float(metrics["loss"]))
        losses[offload] = run
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)
    MeshManager.destroy()
