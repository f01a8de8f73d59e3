"""Pallas TPU kernels for the DPSVRG inner-step elementwise pipeline.

Memory-bound fusions over the flat fp32 parameter buffer:

  svrg_step  — 4 streams in (x, g_now, g_snap, mu) -> 1 out:
               q = x - alpha*(g_now - g_snap + mu).
               Unfused jnp does 3 HBM round trips of intermediates; the
               kernel reads each operand once and writes once
               (arithmetic intensity 4 flops / 20 bytes -> pure bandwidth).
  mix_prox   — 3 streams in (q_self + two ppermuted neighbor buffers) ->
               ring-gossip weighted combine + l1 soft-threshold in one pass.

Tiling: (8, 1024) fp32 blocks — 8 sublanes x (8*128) lanes, a multiple of
the (8, 128) VREG tile, 32 KiB per operand block; with 4 operands + output
the working set is 160 KiB, far under the ~16 MiB VMEM budget, letting the
pipeline run double-buffered at full HBM bandwidth.  1-D grid over rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

__all__ = ["svrg_step_kernel_call", "mix_prox_kernel_call",
           "fused_step_kernel_call", "BLOCK_ROWS", "BLOCK_COLS"]

BLOCK_ROWS = 8
BLOCK_COLS = 1024


def _svrg_step_kernel(alpha_ref, x_ref, gn_ref, gs_ref, mu_ref, q_ref):
    alpha = alpha_ref[0]
    v = gn_ref[...] - gs_ref[...] + mu_ref[...]
    q_ref[...] = x_ref[...] - alpha * v


def _mix_prox_kernel(w_ref, qs_ref, qu_ref, qd_ref, out_ref):
    w_self, w_up, w_down, thresh = w_ref[0], w_ref[1], w_ref[2], w_ref[3]
    z = w_self * qs_ref[...] + w_up * qu_ref[...] + w_down * qd_ref[...]
    out_ref[...] = jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)


def _grid_call(kernel, scalars, operands, interpret: bool):
    """Common 1-D grid launch over (rows, BLOCK_COLS) fp32 buffers."""
    rows = operands[0].shape[0]
    assert rows % BLOCK_ROWS == 0, rows
    block = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec(memory_space=pl.ANY) if False else \
        pl.BlockSpec((scalars.shape[0],), lambda i: (0,))
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[scalar_spec] + [block] * len(operands),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, operands[0].dtype),
        # elementwise over independent row blocks: fully parallel grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scalars, *operands)


def svrg_step_kernel_call(x, g_now, g_snap, mu, alpha, *, interpret: bool):
    """All operands: (rows, BLOCK_COLS) fp32, rows % BLOCK_ROWS == 0."""
    scalars = jnp.asarray([alpha], jnp.float32)
    return _grid_call(_svrg_step_kernel, scalars, (x, g_now, g_snap, mu),
                      interpret)


def mix_prox_kernel_call(q_self, q_up, q_down, w_self, w_up, w_down, thresh,
                         *, interpret: bool):
    scalars = jnp.asarray([w_self, w_up, w_down, thresh], jnp.float32)
    return _grid_call(_mix_prox_kernel, scalars, (q_self, q_up, q_down),
                      interpret)


# ---------------------------------------------------------------------------
# Fused resident step: gossip mix + SVRG correction + prox, one pass
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _make_fused_kernel(rule: str, prox_kind: str, m: int):
    """Kernel body for one (m_pad, block_cols) column tile.

    Every node row is in the tile (m_pad <= a few VREG sublane groups), so
    one grid step sees the full node axis and the whole mix is local; the
    grid only tiles the parameter axis.  The math is delegated to
    ``ref.fused_step_math`` so the kernel is bit-identical to the oracle.
    """

    def body(s_ref, w_ref, *refs):
        *op_refs, out_ref = refs
        streams = tuple(r[...] for r in op_refs)
        out_ref[...] = ref.fused_step_math(
            w_ref[...], streams, s_ref[0], s_ref[1],
            m=m, rule=rule, prox_kind=prox_kind)

    body.__name__ = f"fused_{rule}_{prox_kind}_kernel"
    return body


def fused_step_kernel_call(w, streams, alpha, lam, *, m: int, rule: str,
                           prox_kind: str, interpret: bool):
    """prox(W @ (x - alpha*v)) over stacked (m_pad, d_pad) fp32 buffers.

    ``w``: (m_pad, w_cols) zero-padded mixing matrix, broadcast to every
    grid step.  ``streams``: 4 buffers for rule="svrg" (x, g_now, g_snap,
    mu), 2 for rule="sgd" (x, g).  1-D grid over column tiles of width
    min(d_pad, BLOCK_COLS); per-block working set at the widest tile is
    (len(streams)+1) * m_pad * 1024 * 4 B — 160 KiB at m_pad=8 — well
    inside VMEM with room to double-buffer.
    """
    m_pad, d_pad = streams[0].shape
    assert m_pad % BLOCK_ROWS == 0, m_pad
    assert 0 < m <= m_pad, (m, m_pad)
    block_cols = min(BLOCK_COLS, d_pad)
    assert d_pad % block_cols == 0, (d_pad, block_cols)
    scalars = jnp.stack([jnp.asarray(alpha, jnp.float32),
                         jnp.asarray(lam, jnp.float32)])
    block = pl.BlockSpec((m_pad, block_cols), lambda i: (0, i))
    w_spec = pl.BlockSpec(w.shape, lambda i: (0, 0))
    scalar_spec = pl.BlockSpec((2,), lambda i: (0,))
    return pl.pallas_call(
        _make_fused_kernel(rule, prox_kind, m),
        grid=(d_pad // block_cols,),
        in_specs=[scalar_spec, w_spec] + [block] * len(streams),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(streams[0].shape, streams[0].dtype),
        # column tiles are independent: fully parallel grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scalars, w, *streams)
