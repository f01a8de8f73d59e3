"""Pallas TPU fused RMSNorm.

RMSNorm runs 2x per layer per token across every architecture in the zoo —
a pure bandwidth op (read x, one reduction, scale, write).  Unfused XLA on
TPU usually fuses this fine, but under the layer-scan the norm sits between
matmuls where a dedicated kernel guarantees the single-HBM-pass schedule
and keeps statistics in fp32 regardless of the activation dtype.

Tiling: (block_rows, d) tiles — the model dim stays whole in VMEM (d up to
8192 fp32 = 32 KiB/row; 8 rows = 256 KiB, well inside VMEM), rows stream.
The reduction is per-row, so the grid is embarrassingly parallel over rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["rmsnorm_kernel_call", "BLOCK_ROWS"]

BLOCK_ROWS = 8


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                   # (bр, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * scale * (1.0 + w)[None, :]).astype(o_ref.dtype)


def rmsnorm_kernel_call(x, weight, eps: float = 1e-6, *, interpret: bool):
    """x: (rows, d) with rows % BLOCK_ROWS == 0; weight: (d,)."""
    rows, d = x.shape
    assert rows % BLOCK_ROWS == 0, rows
    kernel = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        # per-row reduction only: the row grid is embarrassingly parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, weight)
