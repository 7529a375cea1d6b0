"""What the readers of `afmoe`'s per-layer metrics share beside ``tower_trace``: the family's
parts and sub-scopes for ``lfm2_trace.exclusive_table`` — the traced train steps' device self
time split so that every operation counts ONCE, by the first of `PARTS` on the operation's name,
and inside a part by the first of `SUB_SCOPES` (the table of PERF.md section 5). The walk is
lfm2's; this file names what it looks for.
"""

from __future__ import annotations

from benchmark import lfm2_trace


def of_this_family(ctx) -> bool:
    """Whether the cell's configuration names this family's modules (``benchmark_modules``)."""
    return ctx.cell.config.get("benchmark_modules", {}).get("weights") == "benchmark.weights_afmoe"


# (part, scopes): an operation belongs to the first part one of whose scopes is on its name; the two
# kinds of attention stand inside ``attention``, so they come before it
PARTS = (
    ("head_loss", ("head_loss", "embed", "final_norm")),
    ("attention_window", ("attention_window",)),
    ("attention_full", ("attention_full",)),
    ("attention_other", ("attention",)),
    ("moe", ("moe",)),
    ("dense_mlp", ("dense_mlp",)),
    ("block_norms", ("block_norms",)),
    ("optimizer", ("optimizer", "grad_clip", "accumulate")),
    ("blocks_other", ("blocks",)),
)
SUB_SCOPES = (
    "qk_norm", "attention_gate", "splash_mha", "moe_router", "moe_dispatch", "moe_experts", "moe_shared_expert", "moe_combine",
)


def exclusive_table(result) -> dict | None:
    """`lfm2_trace.exclusive_table` over this family's parts: None where the run traced no
    train step or its program has nothing under ``attention_window``."""
    return lfm2_trace.exclusive_table(result, parts=PARTS, sub_scopes=SUB_SCOPES, family_part="attention_window")


say_table = lfm2_trace.say_table
