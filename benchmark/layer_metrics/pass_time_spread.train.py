"""Slowest pass over fastest (a ratio, 1.0 at best) of a looped model's scan over passes: the
device time of each iteration of the loop (forward and backward of the pass added, self times
under the ``pass`` scope; ``benchmark/ouro_trace.py`` says how an iteration is found), the mean
over the traced steps of a step's largest over its smallest. The passes run one body over the
same shapes, so anything above 1.0 is a re-layout, a copy or a stall that a pass pays and another
does not. Layer: blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.ouro_trace import pass_seconds


def read(result, ctx):
    passes = int(result.facts.get("cfg", {}).get("total_ut_steps", 0)) if result.facts else 0
    if passes < 2:
        return None
    steps = pass_seconds(result, passes)
    if not steps or any(min(step) <= 0 for step in steps):
        return None
    return sum(max(step) / min(step) for step in steps) / len(steps)
