"""Rotary position embeddings with YaRN scaling, functional.

Parity: reference `hf_models/modeling_utils/position_embedding/rope.py:9-148` (`RoPE`,
`YaRNScaledRoPE`, `apply_rotary_pos_emb`): non-interleaved rotate-half layout (freqs concatenated,
not interleaved), YaRN = interpolation/extrapolation blend via a linear ramp over frequency dims
plus an `mscale` magnitude correction (`0.1*ln(scale)+1`). The reference caches cos/sin in module
buffers; here everything derives from `position_ids` inside the traced function — XLA constant-
folds the inv_freq table and fuses the rest, so a host-side cache buys nothing on TPU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class RoPEParams:
    """Static (trace-time) rotary parameters derived from config."""

    inv_freq: np.ndarray  # [head_dim // 2], float32 — static, constant-folded by XLA
    mscale: float

    @staticmethod
    def from_config(
        head_dim: int,
        base: float = 10000,
        rope_scaling: dict | None = None,
        max_position_embeddings: int = 2048,
    ) -> "RoPEParams":
        dim_range = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
        pos_freqs = base**dim_range

        if rope_scaling is None:
            return RoPEParams(inv_freq=1.0 / pos_freqs, mscale=1.0)

        scaling_type = rope_scaling.get("type", "yarn")
        if scaling_type != "yarn":
            raise ValueError(f"unexpected rope_scaling type '{scaling_type}'")

        scale = rope_scaling.get("factor", 1.0)
        original_max = rope_scaling.get(
            "original_max_position_embeddings", max_position_embeddings
        )
        extrapolation_factor = rope_scaling.get("extrapolation_factor", 1.0)
        attn_factor = rope_scaling.get("attn_factor", 1.0)
        beta_fast = rope_scaling.get("beta_fast", 32)
        beta_slow = rope_scaling.get("beta_slow", 1)

        inv_freq_extrapolation = 1.0 / pos_freqs
        inv_freq_interpolation = 1.0 / (scale * pos_freqs)

        low, high = _yarn_correction_range(beta_fast, beta_slow, head_dim, base, original_max)
        # ramp 0 -> 1 over [low, high]; mask = fraction of EXTRApolation per frequency dim
        inv_freq_mask = (1.0 - _linear_ramp(low, high, head_dim // 2)) * extrapolation_factor
        inv_freq = (
            inv_freq_interpolation * (1.0 - inv_freq_mask) + inv_freq_extrapolation * inv_freq_mask
        )

        mscale = _yarn_get_mscale(scale) * attn_factor
        return RoPEParams(inv_freq=inv_freq.astype(np.float32), mscale=float(mscale))


def get_cos_sin(
    rope: RoPEParams, position_ids: jax.Array, dtype=jnp.float32
) -> tuple[jax.Array, jax.Array]:
    """cos/sin of shape [..., seq, head_dim] for the given position ids [..., seq]."""
    freqs = position_ids[..., None].astype(jnp.float32) * jnp.asarray(rope.inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return (jnp.cos(emb) * rope.mscale).astype(dtype), (jnp.sin(emb) * rope.mscale).astype(dtype)


def apply_rotary_pos_emb(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., seq, heads, head_dim]; cos/sin: [..., seq, head_dim] (broadcast over heads)."""
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    return (x * cos) + (_rotate_half(x) * sin)


def split_qkv_apply_rope(
    qkv: jax.Array,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_cos_sin: tuple[jax.Array, jax.Array] | None,
    qk_norm: tuple[jax.Array, jax.Array, float] | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split the fused QKV projection output and apply rotary embeddings to Q and K.

    THE one rope+QKV call site: training forward, serving prefill chunks, the decode
    step, and the speculative verify window all reach rope through
    `models/modeling_utils.Attention.__call__`, which delegates here — so the XLA
    reference below and the fused Pallas kernel (`ops/pallas/rope_qkv.py`, gated on the
    ``fused_rope_qkv`` family) serve every program from a single seam.

    qkv: [B, S, (num_heads + 2*num_kv_heads) * head_dim] laid out flat
    [Q | K | V] (the repo-wide fused layout, `modeling_utils` module docstring);
    rope_cos_sin: ([..., S, head_dim], [..., S, head_dim]) from `get_cos_sin`, or None
    for rope-free position embeddings (split only). Returns (query [B, S, Hq, D],
    key [B, S, Hkv, D], value [B, S, Hkv, D]) with rope already applied to Q/K.

    `qk_norm` = (query weight [D], key weight [D], eps): an RMSNorm of every query and key
    head over its D columns, BEFORE the rotation (a config with `qk_norm`; scope
    ``qk_norm``). The seam is then split -> norm -> rotate, and the fused kernel — which
    rotates the flat [Q | K | V] before any split — steps aside for the call; the
    ``rope_qkv_plan`` event says so once a model (`why_xla`).
    """
    batch, seq = qkv.shape[:2]

    if rope_cos_sin is not None:
        from .pallas import use_pallas

        if use_pallas("fused_rope_qkv"):
            if qk_norm is None:
                from .pallas.rope_qkv import fused_rope_qkv

                qkv = fused_rope_qkv(
                    qkv, rope_cos_sin[0], rope_cos_sin[1], num_heads, num_kv_heads, head_dim
                )
                rope_cos_sin = None  # rotated in-kernel; plain split below
            else:
                from ..utils.telemetry import get_telemetry

                get_telemetry().event_once(
                    "rope_qkv_plan",
                    form="xla",
                    why_xla="q and k are normed per head between the split and the rotation; "
                    "the fused kernel rotates the flat [Q | K | V] before any split",
                    heads=(num_heads, num_kv_heads, head_dim),
                )

    query, key, value = jnp.split(
        qkv, [num_heads * head_dim, (num_heads + num_kv_heads) * head_dim], axis=-1
    )
    query = query.reshape(batch, seq, num_heads, head_dim)
    key = key.reshape(batch, seq, num_kv_heads, head_dim)
    value = value.reshape(batch, seq, num_kv_heads, head_dim)

    if qk_norm is not None:
        from .normalization import rmsnorm

        query_weight, key_weight, eps = qk_norm
        with jax.named_scope("qk_norm"):
            query = rmsnorm(query, query_weight, eps)
            key = rmsnorm(key, key_weight, eps)

    if rope_cos_sin is not None:
        cos, sin = rope_cos_sin
        query = apply_rotary_pos_emb(query, cos, sin)
        key = apply_rotary_pos_emb(key, cos, sin)
    return query, key, value


def deinterleave_pairs(x: jax.Array) -> jax.Array:
    """``[x0 x1 x2 x3 ...] -> [x0 x2 ... | x1 x3 ...]`` over the last axis: a checkpoint whose
    rotary pairs are neighbours (``rope_interleave``, the DeepSeek-V3 family's layout) brought
    to the rotate-half layout the rest of this file rotates. Scores do not change with the
    order of the columns as long as queries and keys share it."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _yarn_correction_dim(
    num_rotations: float, dim: int, base: float, max_position_embeddings: int
) -> float:
    return (dim * math.log(max_position_embeddings / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base)
    )


def _yarn_correction_range(
    low_rot: float, high_rot: float, dim: int, base: float, max_position_embeddings: int
) -> tuple[int, int]:
    low = math.floor(_yarn_correction_dim(low_rot, dim, base, max_position_embeddings))
    high = math.ceil(_yarn_correction_dim(high_rot, dim, base, max_position_embeddings))
    return max(low, 0), min(high, dim - 1)


def _linear_ramp(low: float, high: float, dim: int) -> np.ndarray:
    if low == high:
        high += 0.001
    ramp = (np.arange(dim, dtype=np.float32) - low) / (high - low)
    return np.clip(ramp, 0.0, 1.0)


def _yarn_get_mscale(scale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * math.log(scale) + 1.0
