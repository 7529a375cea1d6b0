"""Loss-parity harness: identical weights + identical data through BOTH engines.

North-star criterion 2 (BASELINE.md): loss curve within 1% of the GPU-reference baseline.
Evidence protocol (VERDICT r2 item 2):
  1. build a seeded megatron corpus and stream N steps of batches with OUR dataloader stack
  2. init OUR model, export it with save_pretrained (HF layout), load the SAME weights into
     the reference engine's torch model (register_model_classes + from_pretrained)
  3. train both for N steps with the reference's exact training semantics — input=text[:,:-1],
     labels=text[:,1:], fp32-upcast CE over all positions (ref model_wrapper/pretraining.py:
     104-126), global-norm clip 1.0 (ref train_utils.py:95-103), AdamW(lr const, betas
     (0.9, 0.95), eps 1e-10, wd 0.1) — and record both loss curves
  4. write LOSS_PARITY.json; tests/test_loss_parity.py asserts the per-step gap

Runs on CPU (torch cpu + jax cpu), fp32, sdpa both sides. Usage:
  JAX_PLATFORMS=cpu python tools/loss_parity.py [--steps 200]
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_BASE_CONFIG = dict(
    vocab_size=512,
    n_positions=64,
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
)

_FAMILY_CONFIGS = {
    "gpt_dolomite": dict(_BASE_CONFIG, model_type="gpt_dolomite"),
    # aux loss rides the model-internal labels path on BOTH sides (the reference's external
    # pretraining CE never adds aux loss — hf_models/models/moe_dolomite/main.py:112-118 only
    # does with labels + output_router_logits)
    "moe_dolomite": dict(
        _BASE_CONFIG,
        model_type="moe_dolomite",
        num_experts=4,
        num_experts_per_tok=2,
        router_aux_loss_coef=0.01,
    ),
}

CONFIG = _FAMILY_CONFIGS["gpt_dolomite"]
SEQ = 64
MICRO_BS = 8
LR = 3e-4
ADAM = dict(betas=(0.9, 0.95), eps=1e-10, weight_decay=0.1)
CLIP = 1.0


def build_batches(steps: int, workdir: str) -> np.ndarray:
    """Seeded megatron corpus -> [steps, MICRO_BS, SEQ+1] token stream via OUR loader."""
    from dolomite_engine_tpu.data.megatron import MMapIndexedDatasetBuilder
    from dolomite_engine_tpu.data.megatron.gpt_dataset import GPTDataset, GPTDatasetConfig
    from dolomite_engine_tpu.data.megatron.builder import BlendedMegatronDatasetBuilder
    from dolomite_engine_tpu.data.megatron.sampler import MegatronBatchSampler

    rng = np.random.RandomState(1234)
    prefix = os.path.join(workdir, "corpus")
    b = MMapIndexedDatasetBuilder(prefix + ".bin", dtype=np.uint16)
    for _ in range(2000):
        b.add_item(rng.randint(3, CONFIG["vocab_size"], size=rng.randint(20, 120)))
        b.end_document()
    b.finalize(prefix + ".idx")

    class _Tok:
        eos_token_id = CONFIG["eos_token_id"]

    builder = BlendedMegatronDatasetBuilder(
        GPTDataset,
        sizes=[steps * MICRO_BS, 0, 0],
        config=GPTDatasetConfig(
            random_seed=1234,
            sequence_length=SEQ,
            blend=[prefix],
            blend_per_split=[None, None, None],
            split="100,0,0",
            path_to_cache=os.path.join(workdir, "cache"),
            return_document_ids=False,
            fim_rate=0,
            fim_spm_rate=0.5,
        ),
        tokenizer=_Tok(),
        caching_allowed=True,
    )
    train_ds, _, _ = builder.build()
    sampler = MegatronBatchSampler(
        total_samples=len(train_ds),
        consumed_samples=0,
        micro_batch_size=MICRO_BS,
        num_replicas=1,
        rank=0,
    )
    batches = []
    it = iter(sampler)
    for _ in range(steps):
        idx = next(it)
        batches.append(np.stack([np.asarray(train_ds[i]["text"]) for i in idx]))
    return np.stack(batches).astype(np.int64)  # [steps, B, SEQ+1]


def run_tpu_engine(steps: int, batches: np.ndarray, export_dir: str) -> list[float]:
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
        dtype="fp32",
        sequence_length=SEQ,
        reset_attention_mask=False,
        zero_stage=0,
    )
    sched = get_scheduler(0, 0, None, steps + 1, LRDecaySchedule.constant, 0.0, base_lr=LR)
    opt = get_optimizer("TorchAdamW", dict(ADAM), sched)
    state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(1234))

    # identical-weights handoff: HF-layout export the torch side loads verbatim
    wrapper.save_pretrained(export_dir, params=state.params)

    def loss_fn(params, micro, rng):
        return wrapper.loss(params, micro["text"], train=True)

    step_fn = make_train_step(loss_fn, opt, gradient_accumulation_steps=1, gradient_clipping=CLIP)
    losses = []
    with mesh:
        jit_step = jax.jit(step_fn, donate_argnums=0)
        for t in range(steps):
            batch = {"text": jnp.asarray(batches[t])[None]}  # [1, B, SEQ+1] accum axis
            state, metrics = jit_step(state, batch, jax.random.PRNGKey(t))
            losses.append(float(metrics["loss"]))
    return losses


def run_reference_engine(steps: int, batches: np.ndarray, ckpt_dir: str) -> list[float]:
    sys.path.insert(0, "/root/reference")
    # torch-version shim: reference targets an older torch (_Partial was renamed Partial)
    import torch.distributed._tensor.placement_types as _pt

    if not hasattr(_pt, "_Partial"):
        _pt._Partial = _pt.Partial

    import torch
    import torch.nn.functional as F

    is_moe = CONFIG["model_type"] == "moe_dolomite"
    torch.manual_seed(1234)
    if is_moe:
        from dolomite_engine.hf_models.models.moe_dolomite import MoEDolomiteForCausalLM

        model = MoEDolomiteForCausalLM.from_pretrained(
            ckpt_dir,
            attn_implementation="sdpa",
            torch_dtype=torch.float32,
            moe_implementation="eager",
        )
        # the exact aux-loss function the reference model applies
        # (hf_models/models/moe_dolomite/base.py:5,38-41)
        from transformers.models.mixtral.modeling_mixtral import load_balancing_loss_func
    else:
        from dolomite_engine.hf_models import GPTDolomiteForCausalLM

        model = GPTDolomiteForCausalLM.from_pretrained(
            ckpt_dir, attn_implementation="sdpa", torch_dtype=torch.float32
        )
    model.train()
    optimizer = torch.optim.AdamW(
        model.parameters(),
        lr=LR,
        betas=ADAM["betas"],
        eps=ADAM["eps"],
        weight_decay=ADAM["weight_decay"],
    )

    losses = []
    for t in range(steps):
        tokens = torch.from_numpy(batches[t])
        input_ids = tokens[:, :-1]
        labels = tokens[:, 1:]
        if is_moe:
            out = model(input_ids=input_ids, output_router_logits=True)
            logits = out.logits.float()
        else:
            logits = model(input_ids=input_ids).logits.float()
        loss = F.cross_entropy(logits.view(-1, logits.size(-1)), labels.reshape(-1))
        if is_moe:
            aux = load_balancing_loss_func(
                out.router_logits, CONFIG["num_experts"], CONFIG["num_experts_per_tok"]
            )
            loss = loss + CONFIG["router_aux_loss_coef"] * aux
        optimizer.zero_grad()
        loss.backward()
        torch.nn.utils.clip_grad_norm_(model.parameters(), CLIP)
        optimizer.step()
        losses.append(float(loss.detach()))
    return losses


def main() -> None:
    global CONFIG

    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--family", choices=sorted(_FAMILY_CONFIGS), default="gpt_dolomite")
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args()

    CONFIG = _FAMILY_CONFIGS[args.family]
    if args.out is None:
        suffix = "" if args.family == "gpt_dolomite" else f"_{args.family}"
        args.out = os.path.join(
            os.path.dirname(__file__), "..", f"LOSS_PARITY{suffix}.json"
        )

    with tempfile.TemporaryDirectory() as workdir:
        batches = build_batches(args.steps, workdir)
        export_dir = os.path.join(workdir, "shared-init")
        tpu_losses = run_tpu_engine(args.steps, batches, export_dir)
        ref_losses = run_reference_engine(args.steps, batches, export_dir)

    gaps = [abs(a - b) / max(abs(b), 1e-9) for a, b in zip(tpu_losses, ref_losses)]
    result = {
        "steps": args.steps,
        "config": CONFIG,
        "lr": LR,
        "tpu_losses": [round(x, 6) for x in tpu_losses],
        "reference_losses": [round(x, 6) for x in ref_losses],
        "max_rel_gap": max(gaps),
        "final_rel_gap": gaps[-1],
        "tpu_final": tpu_losses[-1],
        "reference_final": ref_losses[-1],
    }
    with open(os.path.abspath(args.out), "w") as f:
        json.dump(result, f, indent=1)
    print(
        f"loss parity over {args.steps} steps: max_rel_gap={max(gaps) * 100:.3f}% "
        f"final: tpu={tpu_losses[-1]:.4f} ref={ref_losses[-1]:.4f}"
    )


if __name__ == "__main__":
    main()
