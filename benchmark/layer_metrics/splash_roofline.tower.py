"""``splash_roofline`` for a model whose layers differ (the `nemotron_h` tower): the share
(%) of its roofline that the splash attention kernel family reached in the traced training
steps, with the attention layers counted from the pattern's ``*`` where the accepted reader
counts every one of ``n_layer``. The same counts (``benchmark/kernels/splash_attention.py``:
forward + backward, causal over the whole packed row, as the accepted reader takes it) over
the same device time (the operations under a ``splash_mha*`` scope). Layer: kernels. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import splash_attention as kernel


def read(result, ctx):
    facts = result.facts
    cfg = facts.get("cfg", {})
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None or "hybrid_override_pattern" not in cfg:
        return None
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    layers = cfg["hybrid_override_pattern"].count("*")
    if seconds <= 0 or not layers:
        return None  # the family lowered to XLA here, or the pattern has no attention layer
    heads, kv, head_dim = cfg["n_head"], cfg["num_key_value_heads"], cfg["attention_head_dim"]
    rows = facts["rows"] * facts["traced_steps"]
    required_flops = kernel.train_flops(layers, heads, head_dim, facts["sequence_length"], rows)
    required_bytes = kernel.train_bytes(layers, heads, kv, head_dim, facts["sequence_length"], rows)
    least, bound = kernel.roofline_seconds(required_flops, required_bytes, ctx.peaks)
    print(f"splash_roofline.tower: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / seconds
