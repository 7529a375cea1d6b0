"""Sharded (tensor-parallel) generation demo — through the real serving path.

Parity: reference `tools/tensor_parallel_inference.py:10-22` — NCCL init +
`GPTDolomiteForCausalLM_TP.from_pretrained` + generate. Under GSPMD there is no `_TP`
class: the same model runs tensor-parallel by loading params with TP shardings over the
mesh. The demo drives the TP-sharded `ServingEngine` (serving/cluster/sharded.py) — the
same jitted chunked-prefill + paged-decode programs production serving runs, with the KV
pool sharded along kv heads — instead of the legacy one-shot `model.generate` loop.

Run (virtual 8-device CPU example):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/tensor_parallel_inference.py --model <dolomite checkpoint dir> --tp 8
"""

import os
import sys
from argparse import ArgumentParser

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402


def main() -> None:
    parser = ArgumentParser()
    parser.add_argument("--model", type=str, required=True, help="dolomite checkpoint dir")
    parser.add_argument("--tp", type=int, default=None, help="tensor parallel size (default: all devices)")
    parser.add_argument("--prompt", type=str, default="def generate():")
    parser.add_argument("--max-new-tokens", type=int, default=64)
    args = parser.parse_args()

    from dolomite_engine_tpu.enums import Mode
    from dolomite_engine_tpu.model_wrapper import ModelWrapperForFinetuning
    from dolomite_engine_tpu.parallel.mesh import MeshManager
    from dolomite_engine_tpu.serving import ServingEngine, serve_batch

    tp = args.tp or jax.device_count()
    MeshManager(tensor_parallel_size=tp)
    mesh = MeshManager.get_mesh()

    model = ModelWrapperForFinetuning(
        mode=Mode.inference,
        model_name=args.model,
        tensor_parallel_word_embeddings=True,
    )
    # TP-sharded from birth: every parameter is placed per the tp sharding rules, never
    # materialized whole on one device (the GSPMD analogue of per-rank sharded loading)
    params = model.load_pretrained_params(args.model, mesh)
    assert model.tokenizer is not None, "serving requires a tokenizer"

    prompt_ids = model.tokenizer(args.prompt, add_special_tokens=False)["input_ids"]
    multiple = 8
    max_len = -(-len(prompt_ids) // multiple) * multiple + args.max_new_tokens
    pad_token_id = next(
        (t for t in (model.tokenizer.pad_token_id, model.eos_token_id) if t is not None), 0
    )
    engine = ServingEngine(
        model.model,
        params,
        num_slots=1,
        max_len=max_len,
        prefill_bucket_multiple=multiple,
        eos_token_id=model.eos_token_id,
        pad_token_id=pad_token_id,
        mesh=mesh,
        sharding_rules=model.sharding_rules(),
    )
    state = serve_batch(
        engine, [dict(prompt_ids=prompt_ids, max_new_tokens=args.max_new_tokens)]
    )[0]

    text = model.tokenizer.decode(state.tokens, skip_special_tokens=True)
    print(f"[tp={tp}] generated {state.num_generated} tokens:")
    print(args.prompt + text)
    stats = engine.stats
    decode_rate = stats.decode_tok_s()
    print(
        f"engine: decode compiles={engine.decode_compiles}, "
        f"ttft={'n/a' if state.ttft_s is None else f'{state.ttft_s * 1e3:.0f}ms'}, "
        f"decode={'n/a' if decode_rate is None else f'{decode_rate:.0f}'} tok/s",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
