"""Model FLOP/s utilization (%) of `ouro` over the traced steps (sync to sync on the host's clock):
required train operations a token (``benchmark/flops_ouro.py``: every block application and every
head reading of the loop counted — four times a block and the head —, no recomputation, attention
by the keys a token of the traffic's documents attends) x tokens a second a chip, over the chip's
bf16 peak (``benchmark/peaks.json``). The cell's share of the whole step's peak: an end-to-end
utilization on the host's clock, not a kernel's roofline share. Layer: train step, device. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark import flops_ouro as flops
from benchmark.ouro_trace import of_this_family


def read(result, ctx):
    facts = result.facts
    if "tokens_per_step" not in facts or ctx.peaks is None or not of_this_family(ctx):
        return None
    tokens_per_s_per_chip = facts["rate_steps"] * facts["tokens_per_step"] / facts["rate_wall_s"] / facts["chips"]
    documents = flops.corpus_documents(ctx.cell.traffic, ctx.seconds, facts["rows"], facts["sequence_length"])
    keys = flops.mean_attended_keys(ctx.cell.traffic["document_tokens"], facts["sequence_length"], documents)
    return 100.0 * flops.train_flops_per_token(facts["cfg"], keys) * tokens_per_s_per_chip / ctx.peaks["bf16_flops_per_s"]
