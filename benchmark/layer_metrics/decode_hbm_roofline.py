"""Share (%) of the HBM roofline that the decode program reached: the bytes one decode call
must read — every weight once (bf16) and the K/V resident for the decoding rows, from the
shapes and the mean live token count the driver sampled — over the chip's bandwidth, over
the median device time of a decode program in the trace. Decode at batch 16 is
memory-bound, so bytes set its roofline. Layer: engine programs, device. Moves
``itl_p95_ms``.
"""

import statistics


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "decode_program" not in facts or ctx.peaks is None:
        return None
    durations = result.trace.program_durations(facts["decode_program"])
    if not durations:
        return None
    bytes_read = facts["weight_bytes"] + facts["mean_live_kv_tokens"] * facts["kv_bytes_per_token"]
    least = bytes_read / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(durations)
