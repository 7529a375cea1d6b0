"""Model FLOP/s utilization (%) of `afmoe` over the traced steps (sync to sync on the host's
clock): required train operations a token (``benchmark/flops_afmoe.py``: no recomputation,
attention by the keys a token of the traffic's documents attends — ``min(place, window)`` on the
window layers —, the routed experts by the slots the program's counter says it routed here) x
tokens a second a chip, over the chip's bf16 peak (``benchmark/peaks.json``). An end-to-end
utilization on the host's clock, not a kernel's roofline share. Layer: train step, device. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark import flops_afmoe as flops
from benchmark.afmoe_trace import of_this_family
from benchmark.tower_trace import routed_slots_per_token


def read(result, ctx):
    facts = result.facts
    if "tokens_per_step" not in facts or ctx.peaks is None or not of_this_family(ctx):
        return None
    tokens_per_s_per_chip = facts["rate_steps"] * facts["tokens_per_step"] / facts["rate_wall_s"] / facts["chips"]
    documents = flops.corpus_documents(ctx.cell.traffic, ctx.seconds, facts["rows"], facts["sequence_length"])
    window = flops.model_dims(facts["cfg"])["window"]
    full_keys, window_keys = flops.attended_keys(ctx.cell.traffic["document_tokens"], facts["sequence_length"], documents, window)
    required = flops.train_flops_per_token(facts["cfg"], full_keys, window_keys, routed_slots_per_token(result))
    return 100.0 * required * tokens_per_s_per_chip / ctx.peaks["bf16_flops_per_s"]
