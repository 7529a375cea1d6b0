"""95th percentile (ms) of the time the window's requests waited in the scheduler's queue:
the engine's own ``queue_wait`` spans (``utils/tracing.RequestTrace``, on the scheduler's
clock), which the traced run switches on. Layer: engine, host. Moves ``ttft_p95_ms``.
"""

from benchmark.traffic import percentile


def read(result, ctx):
    waits = [r["queue_wait_s"] for r in result.requests if r.get("measured") and r.get("queue_wait_s") is not None]
    if not waits:
        return None
    return 1e3 * percentile(waits, 0.95)
