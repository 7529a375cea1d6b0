"""``moe_grouped_matmul_roofline`` for gated (SwiGLU) banks (`joyai_llm_flash`): the share (%)
of their roofline that the routed experts' grouped products reached in the traced training
steps — the least time the chip could take for the rows the program's ``routed_slots`` counter
says it routed to the experts held here (``benchmark/kernels/moe_grouped_matmul_gated.py``:
three products an expert, forward + backward) over the device self time of the operations under
the ``moe_experts`` scope. Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import moe_grouped_matmul_gated as kernel
from benchmark.tower_trace import scope_seconds, step_counters


def read(result, ctx):
    facts = result.facts
    if "kv_lora_rank" not in facts.get("cfg", {}):
        return None
    seconds = scope_seconds(result, kernel.SCOPE)
    events = step_counters(result)
    if not seconds or not events or ctx.peaks is None:
        return None
    routed_rows = float(sum(sum(e["routed_slots"]) for e in events))
    layer_steps = sum(len(e["routed_slots"]) for e in events)
    # the counter is read on the steps the loop syncs; scale to the traced steps where fewer were read
    scale = facts["traced_steps"] / len(events)
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(facts["cfg"], routed_rows * scale),
        kernel.train_bytes(facts["cfg"], routed_rows * scale, layer_steps * scale),
        ctx.peaks,
    )
    print(
        f"moe_grouped_matmul_roofline.gated: {routed_rows:.0f} routed rows in {len(events)} steps, {seconds:.6f} s on the device, "
        f"least {least:.6f} s ({bound}-bound)", flush=True,
    )
    return 100.0 * least / seconds
