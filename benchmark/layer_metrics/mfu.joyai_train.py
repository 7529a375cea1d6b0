"""Model FLOP/s utilization (%) of `joyai_llm_flash` over the traced steps (sync to sync on the
host's clock): required train operations a token (``benchmark/flops_joyai_flash.py``: no
recomputation, the head's two passes, attention by the keys a token of the traffic's documents
attends, the routed experts by the slots the program's counter says it routed here) x tokens a
second a chip, over the chip's bf16 peak (``benchmark/peaks.json``). An end-to-end utilization
on the host's clock, not a kernel's roofline share. Layer: train step, device. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark import flops_joyai_flash as flops
from benchmark.tower_trace import routed_slots_per_token


def read(result, ctx):
    facts = result.facts
    if "tokens_per_step" not in facts or ctx.peaks is None or "kv_lora_rank" not in facts.get("cfg", {}):
        return None
    tokens_per_s_per_chip = facts["rate_steps"] * facts["tokens_per_step"] / facts["rate_wall_s"] / facts["chips"]
    keys = flops.mean_attended_keys(ctx.cell.traffic["document_tokens"], facts["sequence_length"])
    required = flops.train_flops_per_token(facts["cfg"], keys, routed_slots_per_token(result))
    return 100.0 * required * tokens_per_s_per_chip / ctx.peaks["bf16_flops_per_s"]
