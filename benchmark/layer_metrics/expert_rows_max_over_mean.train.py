"""Rows of the fullest held expert over the mean rows of a held expert, averaged over the
layers of experts and the steps the run's rate is read over, from the program's
``step_counters`` (``fullest_expert_rows``, ``routed_slots``): 1.0 is an even split; the
grouped products' time follows the sum, a straggler shows where experts are spread over
chips. Layer: tower blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import step_counters


def read(result, ctx):
    cfg = result.facts.get("cfg", {})
    held = (cfg.get("experts_held") or (0, cfg.get("num_experts", 0)))[1]
    ratios = [
        fullest * held / routed
        for e in step_counters(result)
        for fullest, routed in zip(e["fullest_expert_rows"], e["routed_slots"])
        if routed > 0
    ]
    if not ratios or not held:
        return None
    return sum(ratios) / len(ratios)
