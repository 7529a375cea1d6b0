"""Share (%) of the traced train steps' device busy time spent under ``head_loss`` and ``exit_gate``
in a looped model: the head read over every pass's rows (the chunked loss's forward, its backward
and the chunks' replays), the gate, and the weighting of the passes' cross-entropies by the gate's
distribution (``pass_weighting``, printed apart). A reader of the loop: where the program names
no ``pass_weighting`` it reads nothing. Layer: head and loss. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_table


def read(result, ctx):
    table = scope_table(result)
    if table is None or "pass_weighting" not in table["self_s"] or table["busy_s"] <= 0:
        return None
    seconds = table["self_s"]
    steps = table["steps"]
    print(
        f"loop_head_loss_share.train: ms a step: head_loss {1e3 * seconds.get('head_loss', 0.0) / steps:.2f} "
        f"(pass_weighting {1e3 * seconds['pass_weighting'] / steps:.3f}), exit_gate {1e3 * seconds.get('exit_gate', 0.0) / steps:.3f}", flush=True,
    )
    return 100.0 * (seconds.get("head_loss", 0.0) + seconds.get("exit_gate", 0.0)) / table["busy_s"]
