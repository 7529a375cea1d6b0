"""Hand-written TPU kernel tier (ROADMAP item: benchmark-gated Pallas layer).

Each kernel family sits behind a per-family switch in :mod:`.config` with the plain-XLA
lowering as the numerical reference (``auto`` — the default — promotes proven families
on detected TPU generations and stays XLA everywhere else):

- :mod:`.paged_attention` — ragged paged-attention decode: serving decode/verify reads
  K/V through the page table, skipping unmapped pages and padded positions instead of
  gather-then-mask;
- :mod:`.prefill_attention` — chunked-prefill flash attention through the page table
  (online softmax over the per-page walk) — prefill chunks skip the worst-case
  gathered view too;
- :mod:`.kv_quant` — per-page quantization encode for the int8/fp8 paged KV pool's
  quantize-on-scatter (byte-identical to the XLA reference encoding);
- :mod:`.rmsnorm` — fused RMSNorm(+residual add) inside the transformer block;
- :mod:`.moe` — grouped-GEMM MoE dispatch (sort-by-expert, block-padded segment GEMMs,
  scatter-combine) replacing the dense all-experts einsum;
- :mod:`.fused_ce` — vocab-tiled online-logsumexp chunk reduction for the chunked fused
  LM-head loss (the chunk's logits tiles never leave VMEM);
- :mod:`.mamba2` — the Mamba-2 chunked selective scan, forward and backward, with every
  ``[L, L]`` tensor in VMEM (chosen by `ops/mamba2.mamba2_scan`, not by a family switch);
- :mod:`.rope_qkv` — fused QKV-split + rotary embedding behind the one rope+QKV call
  site shared by training and the serving prefill/decode/verify programs.

Only the config surface is imported eagerly; kernel modules import
`jax.experimental.pallas` and load lazily behind :func:`.config.use_pallas`, so a build
without Pallas still imports this package. Every kernel runs in interpret mode off-TPU
(`utils/packages.pallas_interpret_mode`), which is how the CPU tier-1 parity suite in
`tests/ops/test_pallas_kernels.py` pins the numerics.
"""

from .config import (
    KERNEL_FAMILIES,
    KernelConfig,
    active_kernel_backends,
    get_kernel_config,
    install_kernel_config,
    kernel_backend,
    kernel_overrides,
    platform_default_backend,
    resolved_kernel_backend,
    use_pallas,
)

__all__ = [
    "KERNEL_FAMILIES",
    "KernelConfig",
    "active_kernel_backends",
    "get_kernel_config",
    "install_kernel_config",
    "kernel_backend",
    "kernel_overrides",
    "platform_default_backend",
    "resolved_kernel_backend",
    "use_pallas",
]
