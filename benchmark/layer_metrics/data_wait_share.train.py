"""Share (%) of the measured window's wall time that the train loop waited for data.

Reads the program's telemetry ``step`` records (``t.data``: the queue wait the loop charged
to the prefetcher) for the window's steps, over the window's wall time on the benchmark's
clock. Layer: train loop, host. Moves ``train_tokens_per_s_per_chip``.
"""


def read(result, ctx):
    facts = result.facts
    if "first_measured_step" not in facts:
        return None
    waits = [
        r["t"]["data"]
        for r in result.telemetry
        if r.get("kind") == "step" and facts["first_measured_step"] <= r["step"] <= facts["last_measured_step"]
    ]
    if not waits:
        return None
    return 100.0 * sum(waits) / facts["wall_s"]
