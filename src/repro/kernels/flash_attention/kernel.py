"""Pallas TPU flash attention (forward): online softmax over KV blocks.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) with the KV axis as the
innermost *sequential* dimension; running max / sum / output accumulators
live in VMEM scratch and persist across the KV iterations of one q block
(the canonical TPU flash schedule — q tile stays resident in VMEM, K/V
stream through, the (Sq, Sk) score matrix is never materialized in HBM).

Block shapes default to (128, head_dim) q tiles and (128, head_dim) kv
tiles — MXU-aligned (128 lanes, head_dim a multiple of 8 sublanes is
enforced by the wrapper's padding).

Features needed by the assigned architectures:
  * GQA — the kv BlockSpec index map folds h -> h * KV // H, so each query
    head group reads its shared KV head without materializing the repeat.
  * causal masking with *block skipping*: fully-masked KV blocks are
    skipped via pl.when (no MXU work), partially-masked blocks apply the
    triangle mask.
  * sliding-window masking (h2o-danube, gemma2 local layers).
  * logit softcap (gemma2).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_call", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int | None,
            softcap: float | None, block_q: int, block_k: int,
            num_kv_blocks: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q_start = qi * block_q
    k_start = kj * block_k

    # ---- block-level skip decisions (static per grid point at trace time
    # they are dynamic scalars; pl.when guards the compute) ----------------
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1           # any kv <= max q pos
    if window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)  # any kv in window

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        # fully-masked rows: m_new stays NEG_INF -> exp(0)=1 garbage; zero it
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.where(m_prev > NEG_INF / 2,
                          jnp.exp(m_prev - m_new), 0.0)   # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_call(q, k, v, *, causal: bool = True,
                         sliding_window: int | None = None,
                         softcap: float | None = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd).  Sq % block_q == 0,
    Sk % block_k == 0 (wrapper pads).  Returns (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    kv, sk = k.shape[1], k.shape[2]
    assert h % kv == 0
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=sliding_window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    grid = (b, h, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, kj, kv=kv, h=h:
                         (bi, hi * kv // h, kj, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, kj, kv=kv, h=h:
                         (bi, hi * kv // h, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        # acc/m/l persist across the (sequential, innermost) kv axis of the
        # grid; re-initialized at kj == 0 for every q block.
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
