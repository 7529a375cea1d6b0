"""``splash_roofline`` of a model whose attention layers differ by mask (`afmoe`: head 128, 32
query heads over 4 key/value heads; window layers of 2048 keys among full ones): the share (%) of
its roofline that the splash attention kernel family reached in the traced training steps.
Required operations from ``benchmark/kernels/splash_attention_visited.py``, one call a kind, on
the program's ``splash_blocks_visited_window`` and ``splash_blocks_visited_full`` counters (event
``step_counters``: the kernel's tables summed over the layers of each kind) and the blocks of its
``splash_block_plan`` event, and the whole rows' bytes of every attention layer, over the device
time of the operations under a ``splash_mha*`` scope. Prints the two visited counts a layer
against the causal count and against what ``flops_afmoe.visited_block_pairs`` counts over the
same corpus. Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark import flops_afmoe as flops
from benchmark.afmoe_trace import of_this_family
from benchmark.kernels import splash_attention_visited as kernel
from benchmark.tower_trace import step_counters


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None or not of_this_family(ctx):
        return None
    cfg = facts["cfg"]
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    events = [e for e in step_counters(result) if "splash_blocks_visited_window" in e]
    plans = [r for r in result.telemetry if r.get("kind") == "event" and r.get("event") == "splash_block_plan"]
    if seconds <= 0 or not events or not plans:
        return None  # the family lowered to XLA here, or the program counts no blocks
    scale = facts["traced_steps"] / len(events)
    windowed, full, causal = (float(sum(e[name] for e in events)) * scale for name in ("splash_blocks_visited_window", "splash_blocks_visited_full", "splash_blocks_causal"))
    m = flops.model_dims(cfg)
    heads, kv, head_dim = m["n_head"], m["n_kv"], m["head_dim"]
    block_q, block_kv = plans[-1]["block_q"], plans[-1]["block_kv"]
    window_layers = list(cfg["layer_types"]).count("sliding_attention")
    full_layers = len(cfg["layer_types"]) - window_layers
    rows = facts["rows"] * facts["traced_steps"]
    # (the counters are sums over the layers of a kind already: one layer's worth of a call each)
    required = kernel.train_flops(1, heads, head_dim, block_q, block_kv, windowed) + kernel.train_flops(1, heads, head_dim, block_q, block_kv, full)
    least, bound = kernel.roofline_seconds(
        required, kernel.train_bytes(window_layers + full_layers, heads, kv, head_dim, facts["sequence_length"], rows), ctx.peaks
    )
    per_layer_row = lambda total, layers: total / max(layers * rows, 1)  # noqa: E731
    documents = flops.corpus_documents(ctx.cell.traffic, ctx.seconds, facts["rows"], facts["sequence_length"])
    counted = flops.visited_block_pairs(ctx.cell.traffic["document_tokens"], facts["sequence_length"], documents, m["window"], block_kv)
    print(
        f"splash_roofline.afmoe: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound); block pairs a layer and row: "
        f"window {per_layer_row(windowed, window_layers):.1f}, full {per_layer_row(full, full_layers):.1f}, under the diagonal "
        f"{per_layer_row(causal, window_layers + full_layers):.1f} (flops_afmoe over the corpus law: window {counted[1]:.1f}, full {counted[0]:.1f}, "
        f"under the diagonal {counted[2]:.1f})", flush=True,
    )
    return 100.0 * least / seconds
