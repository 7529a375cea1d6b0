"""``splash_roofline`` for latent attention (`joyai_llm_flash`): the share (%) of its roofline
that the splash attention kernel family reached in the traced training steps at scores over
nope + rope columns and values of v, every block's kernel (the multi-token-prediction module's
too). Required operations and bytes from ``benchmark/kernels/splash_attention_mla.py`` (forward
+ backward, causal over the whole packed row, as the accepted reader takes it: the kernel does
not skip a block for its documents) over the device time of the operations under a
``splash_mha*`` scope. Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.kernels import splash_attention_mla as kernel


def read(result, ctx):
    facts = result.facts
    cfg = facts.get("cfg", {})
    if result.trace is None or "traced_steps" not in facts or ctx.peaks is None or "kv_lora_rank" not in cfg:
        return None
    seconds = result.trace.scope_seconds(kernel.SCOPE_PREFIX)
    if seconds <= 0:
        return None  # the family lowered to XLA here
    layers = cfg["n_layer"] + cfg.get("num_nextn_predict_layers", 0)
    shape = (layers, cfg["n_head"], cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rows = facts["rows"] * facts["traced_steps"]
    least, bound = kernel.roofline_seconds(
        kernel.train_flops(*shape, facts["sequence_length"], rows), kernel.train_bytes(*shape, facts["sequence_length"], rows), ctx.peaks
    )
    print(f"splash_roofline.mla: {seconds:.6f} s on the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / seconds
