"""What the readers of `joyai_llm_flash`'s per-layer metrics share beside ``tower_trace``: the
traced train steps' device self time split so that every operation counts ONCE — the scopes of
``tower_trace.scope_table`` overlap (the multi-token-prediction module's block has latent
attention and experts of its own) — by the first of `PARTS` on the operation's name.
"""

from __future__ import annotations

from benchmark import phases

# (part, scopes): an operation belongs to the first part one of whose scopes is on its name
PARTS = (
    ("mtp_head_loss", ("mtp_head_loss",)),
    ("mtp", ("mtp",)),
    ("head_loss", ("head_loss", "embed", "final_norm")),
    ("latent_attention", ("latent_attention",)),
    ("moe", ("moe",)),
    ("dense_mlp", ("dense_mlp",)),
    ("optimizer", ("optimizer", "grad_clip", "accumulate")),
    ("blocks_other", ("blocks",)),
)
# the scopes inside a part, for the table of PERF.md section 5 (splash_mha* by prefix)
SUB_SCOPES = (
    "mla_q_down", "mla_q_up", "mla_kv_down", "mla_kv_up", "mla_rope", "splash_mha", "mla_out_proj",
    "moe_router", "moe_dispatch", "moe_experts", "moe_shared_expert", "moe_combine", "mtp_combine",
)


def exclusive_table(result) -> dict | None:
    """{"steps", "busy_s", "part_s": {part: s}, "sub_s": {(part, sub-scope): s}} of the first
    chip's operations inside ``jit_train_step``; ``unattributed`` is what no part names. None
    where the run traced no train step or its program names none of these scopes."""
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
        part = next((name for name, scopes in PARTS if components & set(scopes)), "unattributed")
        part_s[part] = part_s.get(part, 0.0) + self_ns / 1e9
        for sub in SUB_SCOPES:
            if any(c == sub or (sub == "splash_mha" and c.startswith(sub)) for c in components):
                sub_s[(part, sub)] = sub_s.get((part, sub), 0.0) + self_ns / 1e9
                break
    if not {"latent_attention", "mtp"} & set(part_s):
        return None
    return {"steps": len(executions), "busy_s": sum(part_s.values()), "part_s": part_s, "sub_s": sub_s}


def say_table(table: dict) -> None:
    """The table, a step's milliseconds and the share of the busy time, on the log."""
    steps, busy = table["steps"], table["busy_s"]
    print(f"joyai_trace: {steps} traced steps, busy {1e3 * busy / steps:.2f} ms a step (self times, every operation once)", flush=True)
    for part, seconds in sorted(table["part_s"].items(), key=lambda kv: -kv[1]):
        print(f"joyai_trace:   {part:18s} {1e3 * seconds / steps:8.2f} ms  {100 * seconds / busy:5.1f}%", flush=True)
        for (owner, sub), sub_seconds in sorted(table["sub_s"].items(), key=lambda kv: -kv[1]):
            if owner == part:
                print(f"joyai_trace:       {sub:18s} {1e3 * sub_seconds / steps:8.2f} ms  {100 * sub_seconds / busy:5.1f}%", flush=True)
