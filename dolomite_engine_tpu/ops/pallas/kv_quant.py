"""Pallas page-quantization kernel (the ``paged_kv_quant`` family).

One program per page: compute the per-kv-head absmax over the page's *valid* token rows,
derive the symmetric scale, and emit the encoded page plus its ``[H]`` scale row in one
VMEM round trip. The XLA reference (`ops/kv_quant.quantize_pages_xla`) performs the same
ops over the whole batch of pages at once; the interpret-mode parity test asserts the two
encodings are BYTE-IDENTICAL (same round/clip/cast sequence), which is what lets the
quantize-on-scatter stay shared between the XLA and Pallas attention paths without the
pool state ever depending on the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# only imported behind the `config.use_pallas` capability gate
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_default(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    from ...utils.packages import pallas_interpret_mode

    return pallas_interpret_mode()


def _quantize_kernel(values_ref, valid_ref, q_ref, scales_ref, *, qmax: float, is_int: bool):
    # layouts the TPU keeps as they are: tokens on the major dim, heads on sublanes,
    # head_dim on lanes — the mask arrives [page_size, 1, 1] and the scales leave
    # [H, 1], so nothing moves between sublanes and lanes
    values = values_ref[0]  # [page_size, H, D] float
    valid = valid_ref[0] != 0  # [page_size, 1, 1]
    masked = jnp.where(valid, values, 0.0)
    amax = jnp.max(jnp.max(jnp.abs(masked), axis=0), axis=-1, keepdims=True)  # [H, 1]
    # reciprocal-multiply, matching quantize_pages_xla exactly (see that function)
    scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / qmax), 1.0).astype(jnp.float32)
    scaled = values / scale[None]
    if is_int:
        scaled = jnp.round(scaled)
    q_ref[0] = jnp.clip(scaled, -qmax, qmax).astype(q_ref.dtype)
    scales_ref[0] = scale


def quantize_pages_pallas(
    values: jax.Array,
    valid: jax.Array,
    qmax: float,
    out_dtype,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Encode ``[N, page_size, H, D]`` float pages; same contract (and bytes) as
    `ops/kv_quant.quantize_pages_xla`. Under a mesh the kernel runs per shard
    (`parallel.sharding.shard_kernel`) with the kv heads over tp, like the pool — every
    (page, head) is encoded on its own."""
    from ...parallel.sharding import kernel_sharding, shard_kernel

    num_pages, _, heads, _ = values.shape
    pages = (values.shape, (None, None, "act_kv_heads", None))
    sharding = kernel_sharding(
        (pages, (valid.shape, (None, None))),
        (pages, ((num_pages, heads), (None, "act_kv_heads"))),
    )
    return shard_kernel(
        lambda values, valid: _quantize_pages_local(values, valid, qmax, out_dtype, interpret),
        sharding,
    )(values, valid)


def _quantize_pages_local(values, valid, qmax: float, out_dtype, interpret: bool | None):
    num_pages, page_size, heads, head_dim = values.shape
    kernel = functools.partial(
        _quantize_kernel,
        qmax=float(qmax),  # dolint: disable=tracer-python-cast (static kernel param)
        is_int=bool(jnp.issubdtype(jnp.dtype(out_dtype), jnp.integer)),  # dolint: disable=tracer-python-cast (static dtype probe)
    )
    # every block's last two dims equal the array's (the TPU's block rule): the mask
    # and the scales carry trailing unit dims instead of [1, page_size] / [1, H] rows
    page_spec = pl.BlockSpec((1, page_size, heads, head_dim), lambda n: (n, 0, 0, 0))
    with jax.named_scope("pallas_paged_kv_quant"):
        q, scales = pl.pallas_call(
            kernel,
            grid=(num_pages,),
            in_specs=[page_spec, pl.BlockSpec((1, page_size, 1, 1), lambda n: (n, 0, 0, 0))],
            out_specs=(page_spec, pl.BlockSpec((1, heads, 1), lambda n: (n, 0, 0))),
            out_shape=(
                jax.ShapeDtypeStruct(values.shape, out_dtype),
                jax.ShapeDtypeStruct((num_pages, heads, 1), jnp.float32),
            ),
            interpret=_interpret_default(interpret),
        )(values, valid.astype(jnp.int32).reshape(num_pages, page_size, 1, 1))
    return q, scales.reshape(num_pages, heads)
