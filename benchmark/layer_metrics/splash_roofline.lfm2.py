"""``splash_roofline`` counted over the block pairs the step's documents made the kernel visit
(`lfm2_moe`: head 64, 32 query heads over 8 key/value heads, one attention block in five): the
share (%) of its roofline that the splash attention kernel family reached in the traced training
steps. Required operations from ``benchmark/kernels/splash_attention_visited.py`` on the
program's ``splash_blocks_visited`` counter (event ``step_counters``) and the blocks of its
``splash_block_plan`` event — not half the square, so that work the tables avoided cannot read as
efficiency — and the whole rows' bytes, over the device time of the operations under a
``splash_mha*`` scope. Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import splash_attention_visited as kernel
from benchmark.lfm2_trace import of_this_family
from benchmark.tower_trace import step_counters


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None or not of_this_family(ctx):
        return None
    cfg = facts["cfg"]
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    layers = list(cfg["layer_types"]).count("full_attention")
    events = [e for e in step_counters(result) if "splash_blocks_visited" in e]
    plans = [r for r in result.telemetry if r.get("kind") == "event" and r.get("event") == "splash_block_plan"]
    if seconds <= 0 or not layers or not events or not plans:
        return None  # the family lowered to XLA here, or the program counts no blocks
    visited = float(sum(e["splash_blocks_visited"] for e in events)) * facts["traced_steps"] / len(events)
    causal = float(sum(e["splash_blocks_causal"] for e in events)) * facts["traced_steps"] / len(events)
    heads, kv, head_dim = cfg["n_head"], cfg["num_key_value_heads"], cfg["n_embd"] // cfg["n_head"]
    rows = facts["rows"] * facts["traced_steps"]
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(layers, heads, head_dim, plans[-1]["block_q"], plans[-1]["block_kv"], visited),
        kernel.train_bytes(layers, heads, kv, head_dim, facts["sequence_length"], rows),
        ctx.peaks,
    )
    print(
        f"splash_roofline.lfm2: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound) over {visited:.0f} visited block pairs "
        f"of {causal:.0f} under the diagonal ({visited / max(causal, 1.0):.3f})", flush=True,
    )
    return 100.0 * least / seconds
