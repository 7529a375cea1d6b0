"""Device milliseconds a traced training step spends outside the blocks on the model's
side: the final norm, the tied head with the chunked loss (forward, backward, and the
chunk's forward replayed by the backward rule: printed apart) and the embedding the head is
tied to (its gather and its gradient's scatter-add) — the part of a step that does not
grow with depth. Self time of the operations of ``jit_train_step`` under the scopes
``head_loss``, ``final_norm`` and ``embed`` (``benchmark/phases.py``). Layer: train step,
device. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark import phases


def read(result, ctx):
    table = phases.table_of(result)
    value = phases.phase_ms(table, "head_loss")
    if value is None:
        return None
    parts = ", ".join(
        f"{scope}.{way} {1e3 * s / table['steps']:.3f}"
        for (scope, way), s in sorted(table["seconds"].items())
        if phases.PHASE_OF_SCOPE[scope] == "head_loss"
    )
    print(f"head_loss_ms.train: {value:.3f} ms a step = {parts}", flush=True)
    return value
