"""``moe_grouped_matmul_roofline`` for gated (SwiGLU) banks, the sizes from the cell's own
weights module (`lfm2_moe`: banks of 1536, 8 held, no shared expert beside them): the share (%) of
their roofline that the routed experts' grouped products reached in the traced training steps —
the least time the chip could take for the rows the program's ``routed_slots`` counter says it
routed to the experts held here (``benchmark/kernels/moe_grouped_matmul_swiglu.py``: three
products an expert, forward + backward) over the device self time of the operations under the
``moe_experts`` scope. It asks ``benchmark_modules["weights"].model_dims`` for ``d``, ``f`` and
``held`` and sniffs no key of a family's config, so it reads any cell with gated banks whose
weights module says those three (``moe_grouped_matmul_roofline.gated`` is this at another
family's sizes: ROADMAP D9 makes them one). Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

import importlib

from benchmark.kernels import moe_grouped_matmul_swiglu as kernel
from benchmark.tower_trace import scope_seconds, step_counters


def read(result, ctx):
    facts = result.facts
    seconds = scope_seconds(result, kernel.SCOPE)
    events = step_counters(result)
    if not seconds or not events or ctx.peaks is None or facts.get("cfg", {}).get("activation_function") != "swiglu":
        return None
    sizes = importlib.import_module(ctx.cell.config["benchmark_modules"]["weights"]).model_dims(facts["cfg"])
    routed_rows = float(sum(sum(e["routed_slots"]) for e in events))
    layer_steps = sum(len(e["routed_slots"]) for e in events)
    # the counter is read on the steps the loop syncs; scale to the traced steps where fewer were read
    scale = facts["traced_steps"] / len(events)
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(sizes["d"], sizes["f"], routed_rows * scale),
        kernel.train_bytes(sizes["d"], sizes["f"], sizes["held"], routed_rows * scale, layer_steps * scale),
        ctx.peaks,
    )
    print(
        f"moe_grouped_matmul_roofline.lfm2: {routed_rows:.0f} routed rows in {len(events)} steps, {seconds:.6f} s on the device, "
        f"least {least:.6f} s ({bound}-bound)", flush=True,
    )
    return 100.0 * least / seconds
