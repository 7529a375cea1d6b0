"""Peak HBM (GiB) of the fullest chip: ``peak_bytes_in_use + peak_bytes_reserved``. On this
runtime live arrays count as in use and a loaded program's temporaries as reserved
(PERF.md, PR 21 finding 8), so the peak a step needed is nearer their sum. Layer: train
step, device. Moves ``train_tokens_per_s_per_chip`` (memory freed is batch or depth gained).
"""


def read(result, ctx):
    stats = result.memory_stats
    if not stats or "tokens_per_step" not in result.facts:
        return None
    return (stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)) / 2**30
