"""Consensus (gossip) primitives over stacked node parameters.

Three wire formats, equivalence-tested against each other, all usable
anywhere a ``phi`` is accepted (``mix_stacked`` dispatches on type):

* dense ``(m, m)`` array — one einsum ``Phi @ x`` over the leading node
  axis.  Under ``jax.jit`` with that axis sharded over the mesh's node axes,
  GSPMD lowers the einsum to an all-gather of all m copies, so a k-round
  multi-consensus whose ``Phi`` product is computed on host costs **one**
  device collective of O(m) bytes.

* :class:`BandedPhi` — the matrix in cyclic-band form; each nonzero band is
  one cyclic shift (``jnp.roll`` on a single device), so ring / TDMA-
  matching schedules (degree <= 2) mix in O(degree) operations.

* :class:`PermutePhi` — the same bands lowered to ``lax.ppermute`` neighbor
  exchanges under ``shard_map`` on a node-axis device mesh: each band is ONE
  collective-permute of the local shard, never materializing the (m, m)
  matrix.  This is how band-structured gossip maps onto the ICI torus, and
  it generalizes the retired LM-trainer-only ``ring_mix_shardmap`` to every
  banded schedule and every rounds policy.

``multi_consensus_matrix`` implements the paper's multi-consensus rule
(k gossip rounds at inner step k, Algorithm 1 line 10) with an optional cap.
Backend selection/accounting lives in :mod:`repro.core.transport`.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import graphs


__all__ = [
    "mix_stacked",
    "multi_consensus_matrix",
    "band_decompose",
    "banded_to_dense",
    "schedule_band_offsets",
    "bands_for_phi",
    "BandedPhi",
    "PermutePhi",
    "mix_stacked_banded",
    "mix_stacked_permute",
    "stack_tree",
    "unstack_tree",
    "node_mean",
    "broadcast_to_nodes",
]


# ---------------------------------------------------------------------------
# Stacked-pytree helpers
# ---------------------------------------------------------------------------

def stack_tree(tree, m: int):
    """Replicate a pytree along a new leading node axis of size m."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), tree)


def unstack_tree(tree, i: int = 0):
    return jax.tree.map(lambda x: x[i], tree)


def node_mean(tree):
    return jax.tree.map(lambda x: x.mean(axis=0), tree)


def broadcast_to_nodes(tree_mean, m: int):
    return stack_tree(tree_mean, m)


# Phi pytree types whose mixing REQUIRES a threaded transport state (error
# feedback, delay buffers, ...): stateless mix_stacked cannot apply them.
# compression/ scenario modules register their types via mark_stateful so
# algorithms that bypass compression.mix_with_state (plain prox-gossip) fail
# loudly instead of silently dropping the state semantics.
_STATEFUL_ONLY: tuple = ()


def mark_stateful(phi_type: type) -> None:
    """Register a phi pytree type as stateful-only (see ``_STATEFUL_ONLY``)."""
    global _STATEFUL_ONLY
    if phi_type not in _STATEFUL_ONLY:
        _STATEFUL_ONLY = _STATEFUL_ONLY + (phi_type,)


def mix_stacked(phi, tree):
    """One consensus application: leaf <- einsum('ij,j...->i...', phi, leaf).

    ``phi`` may be a numpy or jnp (m, m) matrix — typically the host-side
    multi-consensus product, so arbitrary k-round gossip is one contraction —
    or a :class:`BandedPhi` / :class:`PermutePhi`, in which case the
    contraction is dispatched to the O(degree) cyclic-band collectives of
    :func:`mix_stacked_banded` / :func:`mix_stacked_permute`.
    """
    if _STATEFUL_ONLY and isinstance(phi, _STATEFUL_ONLY):
        raise TypeError(
            f"{type(phi).__name__} mixing is stateful (error feedback / "
            f"delay buffers) and cannot run through the stateless "
            f"gossip.mix_stacked: the driven algorithm must route mixing "
            f"through compression.mix_with_state and thread a mix state "
            f"(Algorithm.init_mix_state) — only DPSVRG-family algorithms "
            f"do; dspg/dpg support stateless transports only")
    if isinstance(phi, BandedPhi):
        return mix_stacked_banded(phi.offsets, phi.coeffs, tree)
    if isinstance(phi, PermutePhi):
        return mix_stacked_permute(phi, tree)
    phi = jnp.asarray(phi, dtype=jnp.float32)

    def _mix(leaf):
        flat = leaf.reshape(leaf.shape[0], -1)
        # HIGHEST: at default precision a TPU multiplies f32 operands in one
        # bf16 pass, which moved the paper problem's consensus error by up
        # to 5% on a v5e
        mixed = jnp.matmul(phi.astype(leaf.dtype), flat,
                           precision=jax.lax.Precision.HIGHEST)
        return mixed.reshape(leaf.shape)

    return jax.tree.map(_mix, tree)


def multi_consensus_matrix(schedule: graphs.MixingSchedule, t0: int, k: int,
                           k_max: int | None = None) -> np.ndarray:
    """Phi for the paper's multi-consensus: ``k`` gossip rounds at inner step
    ``k`` (capped at ``k_max`` for production configs), using the schedule's
    time-varying matrices starting at slot ``t0``.
    """
    rounds = k if k_max is None else min(k, k_max)
    return schedule.consensus_rounds(t0, max(rounds, 1))


# ---------------------------------------------------------------------------
# Banded gossip: W = sum_d diag(c_d) P^d  (beyond-paper optimization)
# ---------------------------------------------------------------------------
#
# A dense `phi @ stacked` einsum makes GSPMD all-gather ALL m node copies to
# every device (O(m) bytes + O(m) temp memory).  Every doubly-stochastic
# mixing matrix decomposes exactly into cyclic-shift bands
#     W[i, j] = c_d[i]  where  d = (j - i) mod m,
# so gossip becomes  sum_d c_d * roll(q, -d, axis=0):  each nonzero band is
# ONE collective-permute of the local shard.  Ring/matching graphs have
# degree <= 2, so communication drops from O(m) to O(degree) — numerically
# IDENTICAL to Algorithm 1 (tested), just a different collective schedule.

def band_decompose(w: np.ndarray, tol: float = 1e-12):
    """-> (offsets tuple[int], coeffs (n_bands, m) float32) with
    W = sum_b diag(coeffs[b]) P^{offsets[b]} (P = +1 cyclic shift)."""
    m = w.shape[0]
    offsets, coeffs = [], []
    for d in range(m):
        c = np.array([w[i, (i + d) % m] for i in range(m)], dtype=np.float32)
        if np.abs(c).max() > tol:
            offsets.append(d)
            coeffs.append(c)
    return tuple(offsets), np.stack(coeffs)


def banded_to_dense(offsets: tuple, coeffs):
    """Inverse of :func:`band_decompose`: (offsets, coeffs (n_bands, m)) ->
    dense (m, m) with W[i, (i + d) % m] = coeffs[b][i].

    Traceable in ``coeffs`` (offsets are static), so a ``lax.scan``-sliced
    :class:`BandedPhi` lowers to the dense mixing matrix the fused
    resident-step kernel consumes without leaving the trace.
    """
    coeffs = jnp.asarray(coeffs, jnp.float32)
    m = coeffs.shape[-1]
    rows = jnp.arange(m)
    w = jnp.zeros((m, m), coeffs.dtype)
    for b, d in enumerate(offsets):
        w = w.at[rows, (rows + d) % m].add(coeffs[b])
    return w


def schedule_band_offsets(schedule: graphs.MixingSchedule,
                          rounds: int) -> tuple:
    """Union of band offsets over every `rounds`-product the schedule can
    produce in one period — the STATIC offset set a compiled step must
    support (coefficients stay dynamic)."""
    offs = set()
    for t0 in range(schedule.period):
        phi = schedule.consensus_rounds(t0, rounds)
        o, _ = band_decompose(phi)
        offs.update(o)
    return tuple(sorted(offs))


def bands_for_phi(phi: np.ndarray, offsets: tuple) -> np.ndarray:
    """Coefficients (len(offsets), m) of phi on a FIXED offset set (zeros for
    absent bands).  Raises if phi has mass outside the offset set."""
    m = phi.shape[0]
    full_off, full_c = band_decompose(phi)
    missing = set(full_off) - set(offsets)
    if missing:
        raise ValueError(f"phi has bands {sorted(missing)} outside {offsets}")
    out = np.zeros((len(offsets), m), np.float32)
    idx = {d: i for i, d in enumerate(offsets)}
    for d, c in zip(full_off, full_c):
        out[idx[d]] = c
    return out


@jax.tree_util.register_pytree_node_class
class BandedPhi:
    """A mixing matrix in cyclic-band form, usable anywhere a dense phi is.

    ``offsets`` is the STATIC band-offset set (pytree aux data, so jitted
    steps specialize on it and each ``jnp.roll`` shift stays a compile-time
    constant); ``coeffs`` is the dynamic per-band coefficient array — either
    ``(n_bands, m)`` for a single step or ``(T, n_bands, m)`` when stacked as
    ``lax.scan`` xs, where scan's leaf slicing yields per-step ``(n_bands,
    m)`` coefficients while the offsets ride along as aux.  ``mix_stacked``
    dispatches instances to :func:`mix_stacked_banded`, so every algorithm
    step built on ``prox_gossip_update`` (or calling ``mix_stacked``
    directly) gossips in O(degree) collectives without code changes.
    """

    __slots__ = ("offsets", "coeffs")

    def __init__(self, offsets: tuple, coeffs):
        self.offsets = tuple(offsets)
        self.coeffs = coeffs

    def tree_flatten(self):
        return (self.coeffs,), self.offsets

    @classmethod
    def tree_unflatten(cls, offsets, children):
        return cls(offsets, children[0])

    @classmethod
    def from_dense(cls, phi: np.ndarray, offsets: tuple) -> "BandedPhi":
        """Project a dense phi onto a fixed offset set (raises on leakage)."""
        return cls(offsets, bands_for_phi(np.asarray(phi), offsets))

    def __repr__(self):
        shape = getattr(self.coeffs, "shape", None)
        return f"BandedPhi(offsets={self.offsets}, coeffs.shape={shape})"


def mix_stacked_banded(offsets: tuple, coeffs, tree):
    """Gossip via cyclic-shift bands.  coeffs: (len(offsets), m)."""
    coeffs = jnp.asarray(coeffs, jnp.float32)

    def _mix(leaf):
        out = None
        for b, d in enumerate(offsets):
            shifted = jnp.roll(leaf, -d, axis=0) if d else leaf
            c = coeffs[b].reshape((leaf.shape[0],) + (1,) * (leaf.ndim - 1))
            term = c.astype(leaf.dtype) * shifted
            out = term if out is None else out + term
        return out

    return jax.tree.map(_mix, tree)


# ---------------------------------------------------------------------------
# shard_map collective-permute lowering of banded gossip
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class PermutePhi:
    """A banded mixing matrix lowered to ``lax.ppermute`` neighbor exchanges
    under ``shard_map`` on a node-axis device mesh.

    Same band parameterization as :class:`BandedPhi` (static ``offsets`` +
    dynamic per-band ``coeffs``), but the mesh and its node axis ride along
    as pytree aux data, so jitted steps specialize on them and ``mix_stacked``
    dispatches the mix to per-band collective-permutes of each device's local
    shard — the stacked buffer is never gathered.  ``coeffs`` may be
    ``(n_bands, m)`` for a single step or ``(T, n_bands, m)`` stacked as
    ``lax.scan`` xs, exactly like ``BandedPhi``.  Requires
    ``mesh.shape[axis] == m`` (one node per device along the node axis).
    """

    __slots__ = ("offsets", "mesh", "axis", "coeffs")

    def __init__(self, offsets: tuple, mesh, axis: str, coeffs):
        self.offsets = tuple(offsets)
        self.mesh = mesh
        self.axis = axis
        self.coeffs = coeffs

    def tree_flatten(self):
        return (self.coeffs,), (self.offsets, self.mesh, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, mesh, axis = aux
        return cls(offsets, mesh, axis, children[0])

    @classmethod
    def from_dense(cls, phi: np.ndarray, offsets: tuple, mesh,
                   axis: str) -> "PermutePhi":
        """Project a dense phi onto a fixed offset set (raises on leakage)."""
        return cls(offsets, mesh, axis, bands_for_phi(np.asarray(phi), offsets))

    def __repr__(self):
        shape = getattr(self.coeffs, "shape", None)
        return (f"PermutePhi(offsets={self.offsets}, axis={self.axis!r}, "
                f"coeffs.shape={shape})")


def mix_stacked_permute(phi: PermutePhi, tree):
    """Gossip via per-band ``lax.ppermute`` exchanges of the local shard.

    Numerically identical to :func:`mix_stacked_banded` (same band sum, one
    term per offset); the collective schedule differs: band ``d`` becomes a
    single collective-permute where device ``j`` sends its block to device
    ``(j - d) mod m`` — O(degree) point-to-point wire traffic instead of the
    dense einsum's O(m) all-gather.
    """
    mesh, axis, offsets = phi.mesh, phi.axis, phi.offsets
    m = mesh.shape[axis]
    coeffs = jnp.asarray(phi.coeffs, jnp.float32)

    def _local(c, *leaves):
        # c: (n_bands, 1) this node's coefficient column; leaves: (1, ...)
        out = []
        for x in leaves:
            acc = None
            for b, d in enumerate(offsets):
                if d % m == 0:
                    recv = x
                else:
                    # y_i needs x_{(i+d) mod m}: source j ships to j - d
                    perm = [(j, (j - d) % m) for j in range(m)]
                    recv = jax.lax.ppermute(x, axis, perm)
                cb = c[b].reshape((1,) + (1,) * (x.ndim - 1))
                term = cb.astype(x.dtype) * recv
                acc = term if acc is None else acc + term
            out.append(acc)
        return tuple(out)

    leaves, treedef = jax.tree.flatten(tree)
    shard = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(None, axis),) + tuple(P(axis) for _ in leaves),
        out_specs=tuple(P(axis) for _ in leaves), check_vma=False)
    return jax.tree.unflatten(treedef, list(shard(coeffs, *leaves)))
