"""Share (%) of its roofline that the splash attention kernel family reached in the traced
training steps: the least time the chip could take for the kernel's required operations
and bytes (``benchmark/kernels/splash_attention.py``, forward + backward, causal) over the
summed device time of the operations under a ``splash_mha*`` scope. Layer: kernels. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import splash_attention as kernel
from benchmark.weights import model_dims


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None:
        return None
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    if seconds <= 0:
        return None  # the family lowered to XLA here: nothing to read
    m = model_dims(facts["cfg"])
    rows = facts["rows"] * facts["traced_steps"]
    required_flops = kernel.train_flops(m["n_layer"], m["n_head"], m["head_dim"], facts["sequence_length"], rows)
    required_bytes = kernel.train_bytes(m["n_layer"], m["n_head"], m["n_kv"], m["head_dim"], facts["sequence_length"], rows)
    least, bound = kernel.roofline_seconds(required_flops, required_bytes, ctx.peaks)
    print(f"splash_roofline: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / seconds
