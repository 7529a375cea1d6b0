"""Model FLOP/s utilization (%) of the `nemotron_h` tower over the traced steps (sync to sync
on the host's clock): required train operations a token (``benchmark/flops_nemotron_h.py``:
no recomputation, the routed experts counted by the slots the program's counter says it
routed here) x tokens a second a chip, over the chip's bf16 peak (``benchmark/peaks.json``).
An end-to-end utilization on the host's clock, not a kernel's roofline share. Layer: train
step, device. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark import flops_nemotron_h as flops
from benchmark.tower_trace import routed_slots_per_token


def read(result, ctx):
    facts = result.facts
    if "tokens_per_step" not in facts or ctx.peaks is None or "hybrid_override_pattern" not in facts.get("cfg", {}):
        return None
    tokens_per_s_per_chip = facts["rate_steps"] * facts["tokens_per_step"] / facts["rate_wall_s"] / facts["chips"]
    required = flops.train_flops_per_token(facts["cfg"], facts["sequence_length"], routed_slots_per_token(result))
    return 100.0 * required * tokens_per_s_per_chip / ctx.peaks["bf16_flops_per_s"]
