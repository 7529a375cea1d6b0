"""Train-step timing of one GPTDolomite configuration on the TPU, in one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}, with the device the
number was taken on in `unit`. It runs on a TPU or exits non-zero: there is no CPU run
under a device metric's name, no re-exec, no second kernel to fall back to. This is not
the round's benchmark — ROADMAP.md S1 defines that (cells, traffic, bounds, a peaks table)
— only the one-configuration timing the earlier rounds used, kept runnable until then.

The reference publishes no benchmark numbers (BASELINE.md); vs_baseline reports achieved
MFU / 0.40, the earlier rounds' target.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench.py measures a TPU; jax found {device.platform} ({device.device_kind})")

    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.enums import AttentionImplementation, LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
    from dolomite_engine_tpu.train_utils import (
        get_model_tflops,
        make_train_step,
        run_timed_windows,
    )
    from dolomite_engine_tpu.utils import detect_peak_tflops_per_device, enable_compilation_cache

    enable_compilation_cache()
    peak = detect_peak_tflops_per_device(device)  # an unknown TPU is an error, not a default

    # accumulation folds micro-steps into one jitted call (lax.scan); the fused chunked
    # LM-head loss removes the [B,S,V] logits allocation (largest in the step)
    seq, micro_bs, accum = 2048, 8, 16
    config = dict(
        model_type="gpt_dolomite",
        vocab_size=50304,
        n_positions=seq,
        n_embd=1024,
        n_layer=24,
        n_head=16,
        num_key_value_heads=8,
        attention_head_type="gqa",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        add_bias=False,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        tie_word_embeddings=True,
        fused_lm_head_loss=True,
    )
    steps, windows = 5, 3

    MeshManager()
    mesh = MeshManager.get_mesh()
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training,
        pretrained_config=config,
        dtype="bf16",
        sequence_length=seq,
        attention_implementation=AttentionImplementation.flash_attention_2,
        reset_attention_mask=False,
        zero_stage=3,
    )

    sched = get_scheduler(10, 0, None, 1000, LRDecaySchedule.cosine, 0.1, base_lr=3e-4)
    opt = get_optimizer(
        "TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, sched
    )
    state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(0))

    def loss_fn(params, micro, rng):
        return wrapper.loss(params, micro["text"], train=True)

    step_fn = make_train_step(loss_fn, opt, gradient_accumulation_steps=accum)
    tokens = np.random.RandomState(0).randint(
        0, config["vocab_size"], size=(accum, micro_bs, seq + 1)
    ).astype(np.int32)

    with mesh:
        jit_step = jax.jit(step_fn, donate_argnums=0)
        batch = {"text": jax.device_put(jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp")))}
        rng = jax.random.PRNGKey(1)

        # warmup / compile
        state, metrics = jit_step(state, batch, rng)
        jax.block_until_ready(metrics["loss"])

        # median of independent timing windows, each ending in block_until_ready
        state, window_times = run_timed_windows(jit_step, state, batch, rng, steps, windows)

    step_time = float(np.median(window_times))
    n_devices = jax.device_count()
    tokens_per_sec = accum * micro_bs * seq / step_time
    mfu = get_model_tflops(wrapper.config, accum * micro_bs, seq) / step_time / n_devices / peak
    print(
        json.dumps(
            {
                "metric": "pretrain_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec / n_devices, 2),
                "unit": (
                    f"tokens/s/chip ({device.platform} {device.device_kind} x{n_devices}, "
                    f"mfu={mfu:.3f} of {peak:g} TFLOP/s, step={step_time*1e3:.1f}ms, "
                    f"win[{min(window_times)*1e3:.0f}-{max(window_times)*1e3:.0f}ms "
                    f"x{len(window_times)}])"
                ),
                "vs_baseline": round(mfu / 0.40, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
