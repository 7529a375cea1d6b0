"""The tracing's own check: share (%) of the self time of ``jit_train_step``'s operations
in the traced steps that fell into no phase (``benchmark/phases.py``): operations whose
framework name carries none of the program's phase scopes, and operations XLA made itself
that name no leaf of the train state either (zero fills of the gradient accumulators,
copies). Prints the whole table — every scope and direction in ms a step, the unattributed
time by HLO category — and its sum beside ``device.busy_s``. Layer: train step, device.
Moves ``train_tokens_per_s_per_chip`` (a share that grows says the phase metrics see less
of the step).
"""

from benchmark import phases


def read(result, ctx):
    table = phases.table_of(result)
    if table is None:
        return None
    busy = result.trace.busy_s
    print(
        f"phases: {phases.describe(table)}; phases + unattributed {table['total_s']:.6f} s of "
        f"device.busy_s {busy:.6f} s ({100.0 * table['total_s'] / busy:.2f}%)", flush=True,
    )
    return 100.0 * sum(table["unattributed"].values()) / table["total_s"]
