"""Milliseconds a traced training step in which the device ran nothing *inside* an execution
of the step's program: the gaps (at least 20 us, as ``breakdown.idle_gaps`` takes them) of
the first chip's busy time that lie between the start and the end of a ``jit_train_step``
on the ``XLA Modules`` line (``benchmark/idle_trace.py``). A kernel mends this idle time — a
loop whose trip count the step computes, a ``cond``, a copy the program waits for — where
``idle_between_programs_ms.train`` is mended in the host loop; the two, the gaps under 20 us
and the window's two edges are ``device_idle_share.train`` x the window, and that sum is
printed. Also printed: the ten widest (scope of the operation before -> scope after) pairs
by the program's ``jax.named_scope``s, the gaps a step by count, and the self time of the
step's ``while`` and ``conditional`` events (what a loop waits inside itself, which the busy
union counts as busy). Layer: device (TPU busy and idle). Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark import idle_trace


def in_step(trace, function: str = "train_step"):
    """(ms a step, the table) of the gaps inside executions of ``jit_<function>``; None
    where the trace holds no such program."""
    table = idle_trace.gap_table(trace, function)
    if table is None:
        return None
    return table.inside_ns / 1e6 / table.steps, table


def read(result, ctx):
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    found = in_step(result.trace)
    if found is None:
        return None
    value, table = found
    pairs: dict = {}
    for gap in table.gaps:
        if gap.inside_ns > 0:
            seconds, count = pairs.get((gap.before, gap.after), (0.0, 0))
            pairs[(gap.before, gap.after)] = (seconds + gap.inside_ns, count + 1)
    inside = sum(count for _, count in pairs.values())
    widest = sorted(pairs.items(), key=lambda kv: -kv[1][0])[:10]
    print(f"idle_in_step_ms.train: {table.identity()}; over {table.steps} steps", flush=True)
    print(
        f"idle_in_step_ms.train: {inside / table.steps:.1f} gaps a step inside the program; widest, ms a step (gaps): "
        + "; ".join(f"{before} -> {after} {ns / 1e6 / table.steps:.3f} ({count})" for (before, after), (ns, count) in widest),
        flush=True,
    )
    containers = sorted(idle_trace.container_self_times(result.trace).items(), key=lambda kv: -kv[1])
    print(
        f"idle_in_step_ms.train: self time of the program's loops and conds (counted busy), ms a step: "
        f"{1e3 * sum(s for _, s in containers) / table.steps:.3f} in {len(containers)} places; widest: "
        + "; ".join(f"{label} {1e3 * s / table.steps:.3f}" for label, s in containers[:10]),
        flush=True,
    )
    return value
