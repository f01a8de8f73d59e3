"""Pure-jnp oracle for the fused DPSVRG update kernels.

``fused_step_math`` is the single source of truth for the fused
resident-step computation: the Pallas kernel body calls it per column tile
and ``fused_step_ref`` calls it on the whole padded buffer.  The mix is one
``dot_general`` whose contraction runs over the stacked node rows, so the
kernel and the ref compute the same sums.  They need not round them the
same way: XLA may lower a per-tile dot and a whole-buffer dot differently,
so interpret-mode kernel results agree with the ref to a few f32 ulps
(bitwise at the paper-scale shapes the tests pin, within 1e-6 at
``(8, 131072)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["svrg_step_ref", "mix_prox_ref", "inner_step_ref",
           "fused_step_math", "fused_step_ref", "FUSED_RULES", "FUSED_PROXES"]

# static configuration space of the fused resident step
FUSED_RULES = ("svrg", "sgd")
FUSED_PROXES = ("l1", "sql2", "none")


def svrg_step_ref(x, g_now, g_snap, mu, alpha):
    """q = x - alpha * (g_now - g_snap + mu)   (Algorithm 1 lines 8-9)."""
    v = g_now - g_snap + mu
    return x - alpha * v


def mix_prox_ref(q_self, q_up, q_down, w_self, w_up, w_down, thresh):
    """x = soft_threshold(w_self*q_self + w_up*q_up + w_down*q_down, thresh)

    (ring-gossip combine + l1 prox; Algorithm 1 lines 10-11 with threshold
    = alpha * lambda)."""
    z = w_self * q_self + w_up * q_up + w_down * q_down
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)


def inner_step_ref(x, g_now, g_snap, mu, x_up, x_down, w_self, w_up, w_down,
                   alpha, thresh):
    """Degenerate single-device composition used in shape sweeps: neighbors'
    q are supplied post-permute."""
    q = svrg_step_ref(x, g_now, g_snap, mu, alpha)
    return mix_prox_ref(q, x_up, x_down, w_self, w_up, w_down, thresh)


# ---------------------------------------------------------------------------
# The fused resident step: prox(W @ (x - alpha*v)) in one pass
# ---------------------------------------------------------------------------

def fused_step_math(w, streams, alpha, lam, *, m: int, rule: str,
                    prox_kind: str):
    """One resident inner step over stacked (m_pad, cols) fp32 buffers.

        v   = g_now - g_snap + mu        (rule="svrg"; 4 streams)
              g                          (rule="sgd";  2 streams)
        q   = x - alpha * v
        z   = W[:, :m_pad] @ q           (gossip mix, one dot_general)
        out = prox(z, alpha, lam)        (l1 soft-threshold | sql2 | none)

    ``w`` is the zero-padded (m_pad, w_cols) mixing matrix.  The mix
    contracts over all m_pad stacked rows; padded columns of ``w`` and
    padded rows of ``q`` are zero, so padded terms contribute exact zeros
    and padded rows/cols of the output stay (signed) zero — the prox maps
    0 -> 0, preserving the invariant across steps.  A single f32 dot beats
    the unrolled broadcast multiply-add form ~2x on the CPU backend (XLA
    materialized each broadcast term at LM-scale d).
    """
    if rule == "svrg":
        x, g_now, g_snap, mu = streams
        v = g_now - g_snap + mu
    elif rule == "sgd":
        x, g_now = streams
        v = g_now
    else:
        raise ValueError(f"unknown fused rule {rule!r}; have {FUSED_RULES}")
    q = x - alpha * v
    # HIGHEST: the gossip mix contracts in f32 on the TPU, not in one bf16
    # pass (same choice as gossip.mix_stacked)
    z = jax.lax.dot_general(w[:, :q.shape[0]], q, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if prox_kind == "l1":
        t = alpha * lam
        return jnp.sign(z) * jnp.maximum(jnp.abs(z) - t, 0.0)
    if prox_kind == "sql2":
        return z / (1.0 + alpha * lam)
    if prox_kind == "none":
        return z
    raise ValueError(
        f"unknown fused prox kind {prox_kind!r}; have {FUSED_PROXES}")


def fused_step_ref(w, streams, alpha, lam, *, m: int, rule: str = "svrg",
                   prox_kind: str = "l1"):
    """Whole-buffer oracle: identical math to the kernel, no tiling."""
    return fused_step_math(w, streams, alpha, lam, m=m, rule=rule,
                           prox_kind=prox_kind)
