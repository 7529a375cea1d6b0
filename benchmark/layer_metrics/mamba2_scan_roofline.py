"""Share (%) of its roofline that the Mamba-2 selective scan reached in the traced training
steps: the least time the chip could take for the scan's required operations and bytes
(``benchmark/kernels/mamba2_scan.py``, forward + backward) over the device self time of the
operations under the ``mamba2_scan`` scope. Layer: kernels. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import mamba2_scan as kernel
from benchmark.tower_trace import scope_seconds


def read(result, ctx):
    facts = result.facts
    seconds = scope_seconds(result, kernel.SCOPE)
    if not seconds or ctx.peaks is None or "hybrid_override_pattern" not in facts.get("cfg", {}):
        return None
    tokens = facts["tokens_per_step"] * facts["traced_steps"]
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(facts["cfg"], tokens), kernel.train_bytes(facts["cfg"], tokens), ctx.peaks
    )
    print(f"mamba2_scan_roofline: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / seconds
