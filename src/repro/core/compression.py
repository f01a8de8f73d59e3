"""Compressed gossip with error feedback (beyond-paper extension).

The paper reduces *rounds* (variance reduction needs fewer steps); this
module reduces *bytes per round*: node i transmits an int8-quantized view
of its iterate and keeps the quantization residual in an error-feedback
accumulator (CHOCO-SGD style), so the compression error is compensated over
time instead of accumulating — empirically the optimality gap tracks the
uncompressed run (tests/test_compression.py) at 4x fewer gossip bytes
(int8 vs f32).

    q_send   = Q(q + e)          # symmetric per-leaf int8
    e_next   = (q + e) - q_send  # residual carried forward
    mix over q_send as usual.

The mix over ``q_send`` goes through ``gossip.mix_stacked``, so the
quantized payload rides ANY wire format — dense, :class:`~repro.core.gossip.
BandedPhi`, or :class:`~repro.core.gossip.PermutePhi`.  On a node-axis mesh
(``PermutePhi``) the quantization happens INSIDE the ``shard_map``, before
the collective-permute, so the integer code (+ per-row scale) is what
actually crosses the interconnect and the bits/32 wire accounting is exact
(:func:`compressed_mix_permute`).  :class:`CompressedPhi`
marks a phi whose transport is compressed (the ``compressed`` backend in
:mod:`repro.core.transport`); :func:`mix_with_state` is the dispatching mix
for algorithm steps that thread an error-feedback state.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import gossip, spans

__all__ = ["CompressionState", "init_state", "quantize_leaf",
           "quantize_int_leaf", "compressed_mix", "compressed_mix_permute",
           "CompressedPhi", "mix_with_state", "register_mix_handler"]

# Extension point: phi pytree types (beyond CompressedPhi) with their own
# stateful mix semantics.  {phi_type: handler(phi, tree, state) ->
# (mixed, new_state)}.  Registered types are also marked stateful-only in
# gossip.mix_stacked so stateless call sites fail loudly.
_MIX_HANDLERS: dict = {}


def register_mix_handler(phi_type: type, handler) -> None:
    """Route ``mix_with_state`` calls on ``phi_type`` phis to ``handler``
    (signature ``handler(phi, tree, state) -> (mixed, new_state)``)."""
    _MIX_HANDLERS[phi_type] = handler
    gossip.mark_stateful(phi_type)


class CompressionState(NamedTuple):
    error: Any   # residual pytree, same structure as params


def init_state(tree) -> CompressionState:
    return CompressionState(error=jax.tree.map(jnp.zeros_like, tree))


def quantize_int_leaf(x, bits: int = 8):
    """Symmetric per-node-row quantization, returned as the WIRE payload:
    the integer code (int8 for bits <= 8, int16 above) plus the per-row f32
    scale.  ``code.astype(f32) * scale`` reconstructs exactly what
    :func:`quantize_leaf` returns — integer codes in [-(2^(bits-1)-1),
    2^(bits-1)-1] are exactly representable in f32, so splitting the
    payload from the reconstruction is bitwise-free.

    The max-abs scale is reduced over everything EXCEPT the leading node
    axis: in a decentralized run node i only knows its own row, so a scale
    pooled across rows would be information no node can have.  That includes
    1-D stacked leaves (one scalar parameter per node, shape ``(m,)``):
    each node's scale is its own |x_i| — reducing over axis 0 there would
    silently couple the nodes through a global scale (and crush small-
    magnitude nodes to zero next to large ones)."""
    levels = float(2 ** (bits - 1) - 1)
    axes = tuple(range(1, x.ndim))  # empty for 1-D: per-element == per-node
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / levels
    scale = jnp.maximum(scale, 1e-12).astype(jnp.float32)
    q = jnp.round(x / scale)
    q = jnp.clip(q, -levels, levels)
    code_dtype = jnp.int8 if bits <= 8 else jnp.int16
    return q.astype(code_dtype), scale


def quantize_leaf(x, bits: int = 8):
    """Dequantized view of :func:`quantize_int_leaf` (what the receiver
    reconstructs) — the roofline accounting uses bits/32 of the f32
    bytes."""
    code, scale = quantize_int_leaf(x, bits)
    return code.astype(scale.dtype) * scale


def compressed_mix(phi, tree, state: CompressionState,
                   bits: int = 8) -> tuple[Any, CompressionState]:
    """Gossip over quantized iterates with error feedback.

    Returns (mixed tree, new compression state).  Exact consensus mean is
    NOT preserved per-step (quantization); the error accumulator restores
    it asymptotically.
    """
    if isinstance(phi, gossip.PermutePhi):
        # on a mesh the quantized payload itself must ride the collective
        return compressed_mix_permute(phi, tree, state, bits=bits)
    compensated = jax.tree.map(jnp.add, tree, state.error)
    sent = jax.tree.map(lambda l: quantize_leaf(l, bits), compensated)
    new_error = jax.tree.map(jnp.subtract, compensated, sent)
    mixed = gossip.mix_stacked(phi, sent)
    return mixed, CompressionState(error=new_error)


def compressed_mix_permute(phi: gossip.PermutePhi, tree,
                           state: CompressionState,
                           bits: int = 8) -> tuple[Any, CompressionState]:
    """CHOCO over a node-axis mesh, quantizing BEFORE the collective.

    The composed path (``quantize_leaf`` then ``mix_stacked_permute``) would
    ship the dequantized f32 reconstruction through ``lax.ppermute`` — the
    bits/32 wire accounting would charge for int codes while f32 actually
    crossed the interconnect.  Here each node quantizes its LOCAL row to the
    integer code + per-row scale inside ``shard_map``, the per-band
    collective-permutes move the int payload (plus the O(1)-per-row scale,
    uncharged — it is one f32 per node per leaf against d codes), and
    receivers dequantize locally.  Numerically identical to the composed
    path: dequantization is elementwise per row and ``ppermute`` moves whole
    rows, so ``permute(code) * permute(scale) == permute(code * scale)``
    term by term.  The error-feedback residual is computed from the local
    row's own code and never leaves the shard."""
    mesh, axis, offsets = phi.mesh, phi.axis, phi.offsets
    m = mesh.shape[axis]
    coeffs = jnp.asarray(phi.coeffs, jnp.float32)
    compensated = jax.tree.map(jnp.add, tree, state.error)
    leaves, treedef = jax.tree.flatten(compensated)
    k = len(leaves)

    def _local(c, *leaves_local):
        # c: (n_bands, 1) this node's coefficient column; each local leaf is
        # the (1, ...) row this device owns
        mixed, sent = [], []
        for x in leaves_local:
            code, scale = quantize_int_leaf(x, bits)
            sent.append(code.astype(scale.dtype) * scale)
            acc = None
            for b, d in enumerate(offsets):
                if d % m == 0:
                    code_r, scale_r = code, scale
                else:
                    # y_i needs x_{(i+d) mod m}: source j ships to j - d
                    perm = [(j, (j - d) % m) for j in range(m)]
                    code_r = jax.lax.ppermute(code, axis, perm)
                    scale_r = jax.lax.ppermute(scale, axis, perm)
                recv = code_r.astype(scale_r.dtype) * scale_r
                cb = c[b].reshape((1,) + (1,) * (recv.ndim - 1))
                term = cb.astype(recv.dtype) * recv
                acc = term if acc is None else acc + term
            mixed.append(acc)
        return tuple(mixed) + tuple(sent)

    shard = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(None, axis),) + tuple(P(axis) for _ in leaves),
        out_specs=tuple(P(axis) for _ in range(2 * k)), check_vma=False)
    out = shard(coeffs, *leaves)
    mixed = jax.tree.unflatten(treedef, list(out[:k]))
    sent = jax.tree.unflatten(treedef, list(out[k:]))
    new_error = jax.tree.map(jnp.subtract, compensated, sent)
    return mixed, CompressionState(error=new_error)


@jax.tree_util.register_pytree_node_class
class CompressedPhi:
    """Marks a mixing matrix whose payload rides the wire int-quantized with
    error feedback.  ``inner`` is any phi representation ``mix_stacked``
    accepts (dense array, ``BandedPhi``, ``PermutePhi``) — so compression
    composes with every stateless transport.  ``bits`` is static aux data;
    the inner phi's own leaves stack through ``lax.scan`` xs as usual.
    """

    __slots__ = ("inner", "bits")

    def __init__(self, inner, bits: int = 8):
        self.inner = inner
        self.bits = int(bits)

    def tree_flatten(self):
        return (self.inner,), self.bits

    @classmethod
    def tree_unflatten(cls, bits, children):
        return cls(children[0], bits)

    def __repr__(self):
        return f"CompressedPhi(bits={self.bits}, inner={self.inner!r})"


# stateless mix_stacked would previously die inside jnp.asarray with an
# opaque conversion error; the stateful-only mark turns that into a clear
# "thread a mix state" TypeError
gossip.mark_stateful(CompressedPhi)


def mix_with_state(phi, tree, state: CompressionState | None):
    """Transport-dispatching mix for steps that thread a mix state.

    Stateless phis pass straight through ``gossip.mix_stacked`` (state is
    returned untouched, and may be None); a :class:`CompressedPhi` routes to
    :func:`compressed_mix` with its inner wire format.  The isinstance check
    happens at trace time (phi's type is pytree structure), so jitted steps
    specialize per transport with zero runtime dispatch cost.  Types added
    via :func:`register_mix_handler` (scenario transports) dispatch first.
    """
    with spans.scope(spans.MIX):
        handler = _MIX_HANDLERS.get(type(phi))
        if handler is not None:
            return handler(phi, tree, state)
        if isinstance(phi, CompressedPhi):
            if state is None:
                raise ValueError(
                    "compressed gossip needs an error-feedback "
                    "CompressionState; the driven algorithm must thread a "
                    "mix state (see Algorithm.init_mix_state)")
            return compressed_mix(phi.inner, tree, state, bits=phi.bits)
        return gossip.mix_stacked(phi, tree), state
