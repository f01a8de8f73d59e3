"""Jit wrappers: flat-buffer padding/reshaping around the fused kernels.

``flatten_tree`` / ``unflatten_tree`` convert a parameter pytree to one
padded fp32 buffer of shape (rows, 1024) — the layout the kernels (and the
ppermute ring fast path in repro.core.gossip) operate on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel, ref

__all__ = ["svrg_step", "mix_prox", "flatten_tree", "unflatten_tree",
           "default_interpret", "FUSED_MIN_D", "fused_wins", "stacked_layout",
           "flatten_stacked", "unflatten_stacked", "pad_mix_matrix",
           "tree_node_dim", "fused_step_buf", "fused_resident_step"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


_ROW = kernel.BLOCK_ROWS * kernel.BLOCK_COLS


def flatten_tree(tree):
    """-> (buffer (rows, 1024) f32, aux) with zero padding to a whole tile."""
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    n = flat.shape[0]
    padded = -n % _ROW
    if padded:
        flat = jnp.concatenate([flat, jnp.zeros((padded,), jnp.float32)])
    buf = flat.reshape(-1, kernel.BLOCK_COLS)
    treedef = jax.tree.structure(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    return buf, (treedef, shapes, dtypes, n)


def unflatten_tree(buf, aux):
    treedef, shapes, dtypes, n = aux
    flat = buf.reshape(-1)[:n]
    leaves = []
    off = 0
    for shp, dt in zip(shapes, dtypes):
        size = int(np.prod(shp))
        leaves.append(flat[off:off + size].reshape(shp).astype(dt))
        off += size
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Fused resident step: stacked (m, d) layout + impl routing
# ---------------------------------------------------------------------------

# Below this per-node parameter count the fused path loses to plain XLA:
# the step is dispatch-bound (not memory-bound) and padding the parameter
# axis to a whole 128-lane tile dominates the buffer (paper-scale d=30 pads
# (8, 30) -> (8, 128), 77% padding).  kernel="auto" keeps the unfused XLA
# body there and only swaps the fused body in at LM-sized d.
FUSED_MIN_D = 8192


def fused_wins(d: int) -> bool:
    """Whether kernel="auto" picks the fused body at per-node size ``d``."""
    return int(d) >= FUSED_MIN_D


def stacked_layout(m: int, d: int) -> tuple[int, int, int]:
    """-> (m_pad, d_pad, block_cols) for the fused kernel's (m, d) buffers.

    Rows pad to the 8-sublane tile.  Columns pad to one 128-lane tile for
    narrow paper-scale d (a single-tile grid — NOT the legacy whole
    (8, 1024) flatten_tree tile, which would be >99% padding at d=30), and
    to whole 1024-lane blocks once d is large enough to stream.
    """
    m_pad = -(-m // kernel.BLOCK_ROWS) * kernel.BLOCK_ROWS
    if d <= kernel.BLOCK_COLS:
        d_pad = max(-(-d // 128) * 128, 128)
    else:
        d_pad = -(-d // kernel.BLOCK_COLS) * kernel.BLOCK_COLS
    return m_pad, d_pad, min(d_pad, kernel.BLOCK_COLS)


def flatten_stacked(tree, m: int):
    """Pytree of (m, ...) leaves -> ((m_pad, d_pad) f32 buffer, aux).

    Per-node parameters flatten along axis 1; zero padding on both axes.
    """
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate(
        [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
    d = flat.shape[1]
    m_pad, d_pad, _ = stacked_layout(m, d)
    buf = jnp.pad(flat, ((0, m_pad - m), (0, d_pad - d)))
    treedef = jax.tree.structure(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    return buf, (treedef, shapes, dtypes, m, d)


def unflatten_stacked(buf, aux):
    treedef, shapes, dtypes, m, d = aux
    flat = buf[:m, :d]
    leaves = []
    off = 0
    for shp, dt in zip(shapes, dtypes):
        size = int(np.prod(shp[1:]))
        leaves.append(flat[:, off:off + size].reshape(shp).astype(dt))
        off += size
    return jax.tree.unflatten(treedef, leaves)


def tree_node_dim(tree) -> int:
    """Per-node flattened parameter count of a stacked (m, ...) pytree."""
    return sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(tree))


def pad_mix_matrix(w, m_pad: int):
    """(m, m) mixing matrix -> (m_pad, w_cols) zero-padded for the kernel.

    w_cols is a whole 128-lane tile; padded entries are zero so padded rows
    stay zero through the mix (prox maps 0 -> 0, preserving the invariant
    across steps).
    """
    m = w.shape[0]
    w_cols = max(-(-m_pad // 128) * 128, 128)
    return jnp.pad(jnp.asarray(w, jnp.float32),
                   ((0, m_pad - m), (0, w_cols - m)))


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        # Off-TPU the real kernel can't lower and interpret mode is far too
        # slow for a hot path; the jitted oracle IS the fused path there
        # (same math, one fused XLA computation).  interpret stays
        # available explicitly for kernel-vs-ref tests.
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


def fused_step_buf(w_pad, streams, alpha, lam, *, m: int, rule: str = "svrg",
                   prox_kind: str = "l1", impl: str = "auto"):
    """Buffer-level fused step; trace-safe (called inside resident chunks).

    impl: "auto" (kernel on TPU, jnp oracle elsewhere) | "kernel" |
    "interpret" (Pallas interpret mode, tests only) | "ref".
    """
    impl = _resolve_impl(impl)
    if impl == "ref":
        # f32 scalars exactly as the kernel reads them from its scalar
        # block — keeps ref bit-identical (alpha*lam in f32, not f64).
        alpha = jnp.asarray(alpha, jnp.float32)
        lam = jnp.asarray(lam, jnp.float32)
        return ref.fused_step_ref(w_pad, tuple(streams), alpha, lam, m=m,
                                  rule=rule, prox_kind=prox_kind)
    return kernel.fused_step_kernel_call(
        w_pad, tuple(streams), alpha, lam, m=m, rule=rule,
        prox_kind=prox_kind, interpret=(impl == "interpret"))


def fused_resident_step(w, x_tree, grad_trees, alpha, lam, *, rule: str,
                        prox_kind: str, impl: str = "auto"):
    """Tree-level fused step: prox(W @ (x - alpha*v), alpha*lam).

    ``w``: dense (m, m) mixing matrix (may be a tracer).  ``grad_trees``:
    (g_now, g_snap, mu) for rule="svrg", (g,) for rule="sgd" — all with the
    same stacked (m, ...) structure as ``x_tree``.
    """
    m = jax.tree.leaves(x_tree)[0].shape[0]
    x_buf, aux = flatten_stacked(x_tree, m)
    streams = [x_buf] + [flatten_stacked(t, m)[0] for t in grad_trees]
    w_pad = pad_mix_matrix(w, x_buf.shape[0])
    out = fused_step_buf(w_pad, streams, alpha, lam, m=m, rule=rule,
                         prox_kind=prox_kind, impl=impl)
    return unflatten_stacked(out, aux)


@functools.partial(jax.jit, static_argnames=("interpret",))
def svrg_step(x, g_now, g_snap, mu, alpha, interpret: bool | None = None):
    """q = x - alpha*(g_now - g_snap + mu) over (rows, 1024) fp32 buffers."""
    interpret = default_interpret() if interpret is None else interpret
    return kernel.svrg_step_kernel_call(x, g_now, g_snap, mu, alpha,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mix_prox(q_self, q_up, q_down, w_self, w_up, w_down, thresh,
             interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return kernel.mix_prox_kernel_call(q_self, q_up, q_down, w_self, w_up,
                                       w_down, thresh, interpret=interpret)
