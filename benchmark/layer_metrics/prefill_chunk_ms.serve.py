"""Median device time (ms) of the slowest chunk-prefill program in the traced serving
window. Every chunk program is jitted from one function (``chunk``), so the trace tells
them apart only by their hash; the slowest by median is the full-width (512-token) chunk,
which is what a long prompt pays per budgeted step. Layer: engine programs, device. Moves
``ttft_p95_ms``.
"""

import statistics


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "chunk_program" not in facts:
        return None
    programs = result.trace.program_durations_by_program(facts["chunk_program"])
    if not programs:
        return None
    # programs seen three times or more, where there are any: one slow execution is no median
    seen_often = [d for d in programs.values() if len(d) >= 3] or list(programs.values())
    return 1e3 * max(statistics.median(d) for d in seen_often)
