"""Share (%) of the traced train steps' device busy time spent under the ``pass`` scope — the body
of a looped model's scan over passes: every block application of every pass and the final norm
that closes it, forward, replay and backward, self times. Prints the split inside it (the blocks'
norms, the splash kernels) and each pass's milliseconds. Layer: blocks. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.ouro_trace import PASS_SCOPE, pass_seconds
from benchmark.tower_trace import scope_share, scope_table


def read(result, ctx):
    share = scope_share(result, PASS_SCOPE)
    if share is None:
        return None
    table = scope_table(result)
    steps = table["steps"]
    parts = ", ".join(f"{scope} {1e3 * table['self_s'][scope] / steps:.2f}" for scope in (PASS_SCOPE, "block_norms", "attn", "mlp") if scope in table["self_s"])
    print(f"loop_blocks_share.train: ms a step over {steps} steps, busy {1e3 * table['busy_s'] / steps:.2f}: {parts}", flush=True)
    passes = pass_seconds(result, int(result.facts.get("cfg", {}).get("total_ut_steps", 0)))
    if passes:
        mean = [1e3 * sum(step[t] for step in passes) / len(passes) for t in range(len(passes[0]))]
        print(f"loop_blocks_share.train: ms a pass (forward + backward): {[round(x, 2) for x in mean]}", flush=True)
    return share
