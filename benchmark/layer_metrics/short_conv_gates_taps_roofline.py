"""Share (%) of their roofline that the gated short convolution's gates and taps reached in the
traced training steps: the least time the chip could take to move what a forward and a backward
pass of them must move (``benchmark/kernels/short_conv_gates_taps.py``; memory-bound) over the
device self time of the operations under the ``short_conv_gates_taps`` scope (which holds the
forward's replay under block remat: what a fused kernel with a backward rule of its own is judged
by). Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import short_conv_gates_taps as kernel
from benchmark.tower_trace import scope_seconds


def read(result, ctx):
    facts = result.facts
    seconds = scope_seconds(result, kernel.SCOPE)
    if not seconds or ctx.peaks is None:
        return None  # no operation under the scope: a program without the operator
    cfg = facts["cfg"]
    layers = list(cfg["layer_types"]).count("conv")
    tokens = facts["tokens_per_step"] * facts["traced_steps"]
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(cfg["n_embd"], cfg.get("conv_L_cache", 3), layers, tokens), kernel.train_bytes(cfg["n_embd"], layers, tokens), ctx.peaks
    )
    print(f"short_conv_gates_taps_roofline: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / seconds
