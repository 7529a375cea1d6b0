"""``splash_roofline`` counted over the block pairs the step's documents made the kernel visit, for
a looped model (`ouro`: 16 heads of 128, multi-head; every block holds attention and every block
is applied `total_ut_steps` times): the share (%) of its roofline that the splash attention
kernel family reached in the traced training steps. Required operations from
``benchmark/kernels/splash_attention_visited.py`` on the program's ``splash_blocks_visited``
counter (one attention application's worth a step: the applications share the ids) times the
step's ``n_layer x total_ut_steps`` applications, the whole rows' bytes as often, over the device
time of the operations under a ``splash_mha*`` scope (forward, the replay under block remat, dkv,
dq). Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import splash_attention_visited as kernel
from benchmark.ouro_trace import of_this_family
from benchmark.tower_trace import step_counters


def read(result, ctx):
    facts = result.facts
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None or not of_this_family(ctx):
        return None
    cfg = facts["cfg"]
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    applications = cfg["n_layer"] * cfg.get("total_ut_steps", 1)
    events = [e for e in step_counters(result) if "splash_blocks_visited" in e]
    plans = [r for r in result.telemetry if r.get("kind") == "event" and r.get("event") == "splash_block_plan"]
    if seconds <= 0 or not events or not plans:
        return None  # the family lowered to XLA here, or the program counts no blocks
    visited = float(sum(e["splash_blocks_visited"] for e in events)) * facts["traced_steps"] / len(events)
    causal = float(sum(e["splash_blocks_causal"] for e in events)) * facts["traced_steps"] / len(events)
    heads, kv, head_dim = cfg["n_head"], cfg.get("num_key_value_heads") or cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    rows = facts["rows"] * facts["traced_steps"]
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(applications, heads, head_dim, plans[-1]["block_q"], plans[-1]["block_kv"], visited),
        kernel.train_bytes(applications, heads, kv, head_dim, facts["sequence_length"], rows),
        ctx.peaks,
    )
    print(
        f"splash_roofline.ouro: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound) over {visited:.0f} visited block pairs "
        f"of {causal:.0f} under the diagonal ({visited / max(causal, 1.0):.3f}), {applications} applications a step", flush=True,
    )
    return 100.0 * least / seconds
