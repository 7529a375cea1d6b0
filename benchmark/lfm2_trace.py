"""What the readers of `lfm2_moe`'s per-layer metrics share beside ``tower_trace``: the traced
train steps' device self time split so that every operation counts ONCE, by the first of
`PARTS` on the operation's name, and inside a part by the first of `SUB_SCOPES` — the table of
PERF.md section 5. (``joyai_trace.exclusive_table`` is the same walk with another family's
parts, and returns nothing for a program without latent attention.)
"""

from __future__ import annotations

from benchmark import phases
from benchmark.tower_trace import GROUPED_PRODUCT_SCOPE, UNNAMED_GROUPED_PRODUCT


def of_this_family(ctx) -> bool:
    """Whether the cell's configuration names this family's modules (``benchmark_modules``): what
    a reader that counts with ``flops_lfm2_moe`` / ``weights_lfm2_moe`` dispatches on — the file
    that says which modules a cell runs, not a key sniffed from the program's config."""
    return ctx.cell.config.get("benchmark_modules", {}).get("weights") == "benchmark.weights_lfm2_moe"


# (part, scopes): an operation belongs to the first part one of whose scopes is on its name
PARTS = (
    ("head_loss", ("head_loss", "embed", "final_norm")),
    ("short_conv", ("short_conv",)),
    ("attention", ("attention",)),
    ("moe", ("moe",)),
    ("dense_mlp", ("dense_mlp",)),
    ("optimizer", ("optimizer", "grad_clip", "accumulate")),
    ("blocks_other", ("blocks",)),
)
SUB_SCOPES = (
    "short_conv_in_proj", "short_conv_gates_taps", "short_conv_out_proj", "qk_norm", "splash_mha",
    "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
)


def exclusive_table(result, parts=PARTS, sub_scopes=SUB_SCOPES, family_part: str = "short_conv") -> dict | None:
    """{"steps", "busy_s", "part_s": {part: s}, "sub_s": {(part, sub-scope): s}} of the first
    chip's operations inside ``jit_train_step``; ``unattributed`` is what no part names. None
    where the run traced no train step or its program has nothing under ``family_part``."""
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    device = result.trace.devices[0]
    executions = [m for m in device.modules if m.name.startswith("jit_train_step(")]
    if not executions:
        return None
    program_ids = {m.name[m.name.index("(") + 1 : -1] for m in executions}
    ops = [e for e in device.ops if str(e.stats.get("program_id", "")) in program_ids]
    part_s: dict = {}
    sub_s: dict = {}
    for event, self_ns in phases.self_times(ops):
        path = str(event.stats.get("tf_op", "")).rstrip(":")
        components = {phases.scope_core(c) for c in path.split("/") if c}
        if "/" not in path and event.name.startswith(UNNAMED_GROUPED_PRODUCT):
            components |= {"moe", GROUPED_PRODUCT_SCOPE}
        part = next((name for name, scopes in parts if components & set(scopes)), "unattributed")
        part_s[part] = part_s.get(part, 0.0) + self_ns / 1e9
        for sub in sub_scopes:
            if any(c == sub or (sub == "splash_mha" and c.startswith(sub)) for c in components):
                sub_s[(part, sub)] = sub_s.get((part, sub), 0.0) + self_ns / 1e9
                break
    if family_part not in part_s:
        return None
    return {"steps": len(executions), "busy_s": sum(part_s.values()), "part_s": part_s, "sub_s": sub_s}


def say_table(table: dict) -> None:
    """The table, a step's milliseconds and the share of the busy time, on the log."""
    steps, busy = table["steps"], table["busy_s"]
    print(f"lfm2_trace: {steps} traced steps, busy {1e3 * busy / steps:.2f} ms a step (self times, every operation once)", flush=True)
    for part, seconds in sorted(table["part_s"].items(), key=lambda kv: -kv[1]):
        print(f"lfm2_trace:   {part:18s} {1e3 * seconds / steps:8.2f} ms  {100 * seconds / busy:5.1f}%", flush=True)
        for (owner, sub), sub_seconds in sorted(table["sub_s"].items(), key=lambda kv: -kv[1]):
            if owner == part:
                print(f"lfm2_trace:       {sub:22s} {1e3 * sub_seconds / steps:8.2f} ms  {100 * sub_seconds / busy:5.1f}%", flush=True)
