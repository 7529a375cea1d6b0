"""Host milliseconds between one training step's results reaching the host and the next
step's dispatch having returned: what the device waits for when the loop reads the loss
every step. From the program's telemetry ``step`` records (``t.split``: the loop thread's
spans, in the order they ran): the parts of step N after its ``loop.sync`` (account, log,
checkpoint, poll) plus the parts of step N+1 up to and with its dispatch (the record's own
write, the window record, data wait, rng, ``train_step``); the median over the window's
steps. The slowest step and its split are printed. Layer: train loop, host. Moves
``train_tokens_per_s_per_chip``.
"""

import statistics

SYNC = "loop.sync"


def head_and_tail(split: dict) -> tuple | None:
    """Seconds of an iteration's spans before its sync (the head) and after it (the tail)."""
    names = list(split)
    if SYNC not in names:
        return None
    at = names.index(SYNC)
    return sum(split[n] for n in names[:at]), sum(split[n] for n in names[at + 1 :])


def read(result, ctx):
    facts = result.facts
    if "first_measured_step" not in facts:
        return None
    records = {
        r["step"]: r["t"]
        for r in result.telemetry
        if r.get("kind") == "step" and "split" in r.get("t", {})
        and facts["first_measured_step"] - 1 <= r["step"] <= facts["last_measured_step"]
    }
    parts = {step: head_and_tail(t["split"]) for step, t in records.items()}
    between = [
        parts[step - 1][1] + parts[step][0]
        for step in parts
        if parts[step] is not None and parts.get(step - 1) is not None
    ]
    if not between:
        return None
    step, slowest = max(records.items(), key=lambda kv: kv[1]["wall"])
    worst_gap = max(abs(t["wall"] - sum(t["split"].values())) for t in records.values())
    print(
        f"host_between_steps_ms.train: median of {len(between)} steps; slowest iteration: step {step}, "
        f"{1e3 * slowest['wall']:.3f} ms = "
        + ", ".join(f"{name} {1e3 * s:.3f}" for name, s in slowest["split"].items())
        + f"; widest gap between an iteration's wall time and its parts {1e3 * worst_gap:.3f} ms", flush=True,
    )
    return 1e3 * statistics.median(between)
