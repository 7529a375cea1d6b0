"""Compiled-program perf ledger: hardware-free regression gates over HLO/memory signatures.

    python -m tools.perf_ledger --check            # diff the tree against PERF_LEDGER.json
    python -m tools.perf_ledger --update           # re-baseline the current platform
    python -m tools.perf_ledger --json             # BENCH-trajectory-style line per program
    python -m tools.perf_ledger --programs 'fused_ce.*' --check   # subset (tests, triage)

Captures `utils/program_signature.py` signatures for the canonical hot-program suite —
gpt_dolomite + moe_dolomite train steps under each remat policy, the chunked fused-CE
forward and grad programs, and the serving engine's chunk-prefill/decode/verify programs
at a fixed tiny engine config (paged, + int8 KV and n-gram-speculation variants) — and
diffs them against the committed, platform-keyed `PERF_LEDGER.json` with per-metric
tolerances (`program_signature.DEFAULT_TOLERANCES`). Everything is lower+compile
introspection on miniature shapes: no program executes long, no accelerator claim is
needed, so compile-count regressions, lost donation, remat-policy HBM drift, and
accidental logits materialization all turn into a red `--check` on the CPU tier
(docs/OBSERVABILITY.md "Perf ledger"; the TPU tier still owes wall-clock BENCH lines,
docs/PERFORMANCE.md).

`--check` exits nonzero on drift, naming each metric and delta. Entries are keyed by
`jax.default_backend()`, so a TPU baseline can be added later (`--update` on a TPU host)
without schema changes. A baseline captured under a different jax/jaxlib version or
device count is compared informationally (warnings, exit 0) unless `--strict`: XLA is
free to change its lowering across versions, and gating that would punish the wrong
change.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

DEFAULT_LEDGER = os.path.join(_REPO_ROOT, "PERF_LEDGER.json")

# one tiny-but-real shape set shared by every suite entry: large enough that remat /
# fused-CE decisions show up in temp bytes, small enough that a full capture stays in CI
# budget
# vocab is deliberately a prime: no hidden/MLP activation can share the [B, S, V] shape,
# so the "full logits never materialize" check cannot false-positive on an MLP tensor
_TRAIN = dict(vocab=499, seq=128, n_embd=64, n_layer=2, n_head=4, kv_heads=2, micro_bs=2,
              loss_chunk=64)
_CE = dict(B=2, S=64, H=16, V=199, chunk=8)
_SERVE = dict(num_slots=2, max_len=64, page_size=8, prefill_chunk_tokens=16,
              prompt_len=12, max_new=6)


def _train_step_suite(model_type: str):
    """One capture per remat policy of the full jitted train step (ZeRO-3-style state,
    donated, fused chunked CE)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.enums import AttentionImplementation, LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.models.gpt_dolomite import REMAT_POLICY_NAMES
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
    from dolomite_engine_tpu.train_utils import make_train_step
    from dolomite_engine_tpu.utils.program_signature import capture_jit_signature

    t = _TRAIN
    config = dict(
        model_type=model_type,
        vocab_size=t["vocab"],
        n_positions=t["seq"],
        n_embd=t["n_embd"],
        n_layer=t["n_layer"],
        n_head=t["n_head"],
        num_key_value_heads=t["kv_heads"],
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
        loss_chunk_size=t["loss_chunk"],
    )
    if model_type == "moe_dolomite":
        config.update(num_experts=4, num_experts_per_tok=2, router_aux_loss_coef=0.01)

    MeshManager()
    mesh = MeshManager.get_mesh()
    tokens = np.zeros((1, t["micro_bs"], t["seq"] + 1), np.int32)

    for policy in REMAT_POLICY_NAMES:
        wrapper = ModelWrapperForPretraining(
            mode=Mode.training,
            pretrained_config=config,
            dtype="fp32",
            sequence_length=t["seq"],
            attention_implementation=AttentionImplementation.sdpa,
            zero_stage=3,
            gradient_checkpointing_args={"checkpoint_every": 1, "policy": policy},
        )
        sched = get_scheduler(2, 0, None, 10, LRDecaySchedule.cosine, 0.1, base_lr=1e-3)
        opt = get_optimizer(
            "TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, sched
        )
        state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(0))
        step_fn = make_train_step(
            lambda params, micro, rng, fp8_state=None: wrapper.loss(
                params, micro["text"], train=True, fp8_state=fp8_state
            ),
            opt,
        )
        with mesh:
            batch = {
                "text": jax.device_put(
                    jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp"))
                )
            }
            jit_step = jax.jit(step_fn, donate_argnums=0)
            with wrapper.apply_scope():  # the rules the step's own trace resolves under
                checks = _logits_checks(
                    t["micro_bs"], t["seq"], t["n_embd"], t["vocab"], t["loss_chunk"]
                )
            yield f"train_step[{model_type},policy={policy}]", capture_jit_signature(
                jit_step,
                (state, batch, jax.random.PRNGKey(1)),
                name=f"train_step[{model_type},policy={policy}]",
                shape_checks=checks,
            )


def _logits_checks(batch: int, seq: int, hidden: int, vocab: int, chunk: int) -> dict:
    """Fused CE: the [batch, seq, vocab] fp32 logits of the plain path must not exist. An
    undifferentiated program holds the scan's [batch, chunk, vocab] tile; a differentiated
    one (the summed rule: `ops/loss._chunked_ce_terms_fwd`) keeps one token block's logits
    — [chunks a block, batch, chunk, vocab], as `ops/loss.plan_loss_blocks` cuts them under
    the ambient mesh and its budget in bytes — and nothing twice (the checks are textual:
    a chunk's shape is the tail of a block's, so `chunk_logits` reads true there too)."""
    from dolomite_engine_tpu.ops.loss import plan_loss_blocks

    n_chunks = seq // chunk
    block = plan_loss_blocks(batch, n_chunks, chunk, vocab, hidden, 4)[0].logits_block(
        batch, n_chunks, chunk, vocab
    )
    return {
        "full_logits": ((batch, seq, vocab), "f32"),
        "chunk_logits": ((batch, chunk, vocab), "f32"),
        "block_logits": (block, "f32"),
    }


def _fused_ce_suite():
    """The chunked fused-CE forward and grad programs at a fixed odd-vocab shape — the
    '[B,S,V] never materializes' claim as a standing signature check (the assertion
    tests/ops/test_pallas_kernels.py makes on the lowered text, kept red-able here)."""
    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy
    from dolomite_engine_tpu.utils.program_signature import capture_program_signature

    c = _CE
    hidden = jax.ShapeDtypeStruct((c["B"], c["S"], c["H"]), jnp.float32)
    table = jax.ShapeDtypeStruct((c["V"], c["H"]), jnp.float32)
    labels = jax.ShapeDtypeStruct((c["B"], c["S"]), jnp.int32)
    checks = _logits_checks(c["B"], c["S"], c["H"], c["V"], c["chunk"])

    def fwd(h, t, y):
        return fused_linear_cross_entropy(
            h, t, y, chunk_size=c["chunk"], compute_dtype=jnp.float32
        )

    yield "fused_ce_chunk_fwd", capture_program_signature(
        fwd, hidden, table, labels, name="fused_ce_chunk_fwd", shape_checks=checks
    )
    yield "fused_ce_chunk_grad", capture_program_signature(
        jax.grad(fwd, argnums=(0, 1)),
        hidden,
        table,
        labels,
        name="fused_ce_chunk_grad",
        shape_checks=checks,
    )


def _make_serving_model():
    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu.models.config import CommonConfig
    from dolomite_engine_tpu.models.gpt_dolomite import GPTDolomiteForCausalLM

    config = CommonConfig(
        vocab_size=2048,
        n_positions=512,
        n_embd=32,
        n_layer=4,
        n_head=4,
        num_key_value_heads=2,
        attention_head_type="gqa",
        position_embedding_type="rope",
        add_bias=True,
        activation_function="gelu_pytorch_tanh",
        normalization_function="rmsnorm",
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        bos_token_id=0,
        eos_token_id=1,
        pad_token_id=2,
    )
    model = GPTDolomiteForCausalLM(config=config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return config, model, params


def _drive_engine(engine, config):
    import numpy as np

    s = _SERVE
    rs = np.random.RandomState(0)
    for _ in range(2):
        engine.submit(
            list(map(int, rs.randint(3, config.vocab_size, s["prompt_len"]))),
            max_new_tokens=s["max_new"],
        )
    engine.drain()


def _serving_suite():
    """The serving engine's jitted programs at one fixed tiny config, captured through
    `ServingEngine.program_signatures()`: chunked prefill + decode from the paged
    engine, the same decode under int8 quantized KV, and the speculative verify step."""
    from dolomite_engine_tpu.serving import ServingEngine

    s = _SERVE
    config, model, params = _make_serving_model()
    common = dict(
        num_slots=s["num_slots"],
        max_len=s["max_len"],
        paged=True,
        page_size=s["page_size"],
        prefill_chunk_tokens=s["prefill_chunk_tokens"],
    )

    engine = ServingEngine(model, params, **common)
    _drive_engine(engine, config)
    for name, sig in engine.program_signatures().items():
        yield f"serving.paged:{name}", sig

    engine_int8 = ServingEngine(model, params, kv_dtype="int8", **common)
    _drive_engine(engine_int8, config)
    for name, sig in engine_int8.program_signatures(names=("decode",)).items():
        yield f"serving.int8:{name}", sig

    engine_spec = ServingEngine(model, params, speculate_ngram=True, draft_k=3, **common)
    _drive_engine(engine_spec, config)
    for name, sig in engine_spec.program_signatures(names=("verify",)).items():
        yield f"serving.spec:{name}", sig


def _build_groups():
    """(representative names, lazy builder) per suite group — the probes let a
    `--programs` regex skip building the models a subset capture does not need (the
    final per-name filter is still exact)."""
    policies = ("full", "save_dots", "save_attention_out", "offload_dots")
    serving_probes = (
        "serving.paged:decode",
        "serving.paged:chunk[w=64,final=True]",
        "serving.paged:chunk[w=64,final=False]",
        "serving.int8:decode",
        "serving.spec:verify",
    )
    return (
        (
            tuple(f"train_step[gpt_dolomite,policy={p}]" for p in policies),
            lambda: _train_step_suite("gpt_dolomite"),
        ),
        (
            tuple(f"train_step[moe_dolomite,policy={p}]" for p in policies),
            lambda: _train_step_suite("moe_dolomite"),
        ),
        (("fused_ce_chunk_fwd", "fused_ce_chunk_grad"), _fused_ce_suite),
        (serving_probes, _serving_suite),
    )


def iter_suite(pattern: str | None = None):
    """Yield (program name, ProgramSignature) for every canonical program whose name
    matches `pattern` (regex, None = all). Whole groups whose representative names all
    miss the regex are never built, so a subset capture stays cheap."""
    regex = re.compile(pattern) if pattern else None
    for probes, build in _build_groups():
        if regex is not None and not any(regex.search(p) for p in probes):
            continue
        for name, sig in build():
            if regex is None or regex.search(name):
                yield name, sig


def capture_programs(pattern: str | None = None) -> dict[str, dict]:
    return {name: sig.to_json() for name, sig in iter_suite(pattern)}


def current_env() -> dict:
    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "device_count": jax.device_count(),
    }


def load_ledger(path: str) -> dict:
    if not os.path.exists(path):
        return {"schema": 1, "platforms": {}}
    with open(path) as f:
        return json.load(f)


def save_ledger(path: str, ledger: dict) -> None:
    with open(path, "w") as f:
        json.dump(ledger, f, indent=1, sort_keys=True)
        f.write("\n")


def check_programs(
    baseline_entry: dict,
    current: dict[str, dict],
    pattern: str | None = None,
    strict: bool = False,
) -> tuple[int, list[str]]:
    """Diff current programs against one platform's baseline entry. Returns (exit code,
    report lines). Version/device skew downgrades drift to warnings unless strict."""
    from dolomite_engine_tpu.utils.program_signature import diff_programs

    regex = re.compile(pattern) if pattern else None
    baseline = {
        name: sig
        for name, sig in (baseline_entry.get("programs") or {}).items()
        if regex is None or regex.search(name)
    }
    drifts, notes = diff_programs(baseline, current)

    env = current_env()
    captured = baseline_entry.get("captured") or {}
    skew = [
        f"{key}: baseline {captured.get(key)} vs current {env.get(key)}"
        for key in ("jax", "jaxlib", "device_count")
        if captured.get(key) != env.get(key)
    ]
    informational = bool(skew) and not strict

    lines: list[str] = []
    for note in notes:
        lines.append(f"NOTE {note}")
    if skew:
        lines.append(
            "baseline environment skew (" + "; ".join(skew) + ") — "
            + ("drift below is informational; re-run with --strict to gate"
               if informational else "gating anyway (--strict)")
        )
    for drift in drifts:
        lines.append(("WARN " if informational else "DRIFT ") + str(drift))
    if drifts and not informational:
        lines.append(
            f"FAIL: {len(drifts)} metric(s) drifted past tolerance "
            f"(PERF_LEDGER.json; --update to re-baseline an intended change)"
        )
        return 1, lines
    lines.append(
        f"OK: {len(current)} program(s) within tolerance of the "
        f"{'(skewed) ' if skew else ''}baseline"
        if baseline
        else "OK: no baseline programs matched (nothing gated)"
    )
    return 0, lines


def _json_line(name: str, sig: dict, drifted: bool) -> str:
    return json.dumps(
        {
            "bench": "perf_ledger",
            "program": name,
            "platform": sig.get("platform"),
            "flops": (sig.get("cost") or {}).get("flops"),
            "temp_bytes": (sig.get("memory") or {}).get("temp_size_in_bytes"),
            "donated_inputs": (sig.get("donation") or {}).get("donated_inputs"),
            "compiles": sig.get("compiles"),
            "checks": (sig.get("hlo") or {}).get("checks"),
            "drift": drifted,
        }
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--ledger", default=DEFAULT_LEDGER, help="baseline JSON path")
    parser.add_argument("--check", action="store_true", help="diff vs baseline; exit 1 on drift")
    parser.add_argument("--update", action="store_true", help="re-baseline this platform")
    parser.add_argument("--json", action="store_true", help="one BENCH-style line per program")
    parser.add_argument(
        "--programs", default=None, help="regex restricting capture AND comparison"
    )
    parser.add_argument(
        "--strict",
        action="store_true",
        help="gate even when the baseline was captured under a different jax/jaxlib "
        "version or device count",
    )
    args = parser.parse_args(argv)
    if not (args.check or args.update or args.json):
        parser.error("pick at least one of --check / --update / --json")

    import jax

    platform = jax.default_backend()
    ledger = load_ledger(args.ledger)
    entry = (ledger.get("platforms") or {}).get(platform)

    if args.check and not args.update and entry is None:
        print(
            f"no '{platform}' baseline in {args.ledger} — nothing to gate on this "
            "platform (run --update here to add one)"
        )
        return 0

    print(f"capturing program signatures ({platform})...", file=sys.stderr)
    current = capture_programs(args.programs)

    exit_code = 0
    drifted_names: set[str] = set()
    if args.check and entry is not None:
        exit_code, lines = check_programs(
            entry, current, pattern=args.programs, strict=args.strict
        )
        from dolomite_engine_tpu.utils.program_signature import diff_programs

        drifts, _ = diff_programs(
            {
                name: sig
                for name, sig in (entry.get("programs") or {}).items()
                if name in current
            },
            current,
        )
        drifted_names = {d.program for d in drifts}
        for line in lines:
            print(line)

    if args.json:
        for name, sig in current.items():
            print(_json_line(name, sig, name in drifted_names))

    if args.update:
        platforms = ledger.setdefault("platforms", {})
        if args.programs and entry is not None:
            merged = dict(entry.get("programs") or {})
            merged.update(current)
        else:
            merged = current
        platforms[platform] = {"captured": current_env(), "programs": merged}
        ledger["schema"] = 1
        save_ledger(args.ledger, ledger)
        print(f"baseline updated: {len(merged)} '{platform}' program(s) -> {args.ledger}")

    return exit_code


if __name__ == "__main__":
    raise SystemExit(main())
