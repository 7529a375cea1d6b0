"""Median gap (ms) on the device between the end of one engine program and the start of the
next, over the traced serving window: what the host's ``ServingEngine.step`` costs the chip
between launches. Gaps longer than 50 ms are the engine having no work (waiting for an
arrival) and are left out. Layer: engine, host. Moves ``itl_p95_ms``.
"""

import statistics


def read(result, ctx):
    if result.trace is None or "traced_serve_s" not in result.facts:
        return None
    gaps = [g for g in result.trace.program_gaps() if g < 0.05]
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
