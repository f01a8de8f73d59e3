"""First-class gossip transports: HOW the doubly-stochastic mixing moves
parameters between nodes.

DPSVRG's convergence argument (Algorithm 1 + Theorem 1) only constrains the
mixing product ``Phi(l, g)`` — it is agnostic to the wire format that
executes it.  This module makes that axis a plugin, the same way
``core.algorithm`` made the method a plugin: a :class:`GossipBackend` owns

* ``prepare(schedule, meta, mesh=None) -> aux`` — static precompute (band
  offset unions, node-axis mesh setup) done once per run,
* ``phi_for(aux, slot, rounds) -> phi`` — the host-side per-step wire
  representation (a plain ``(m, m)`` array, a :class:`~repro.core.gossip.
  BandedPhi`, a :class:`~repro.core.gossip.PermutePhi`, ...).  Every
  representation is a pytree, so the runner stacks it through ``lax.scan``
  xs generically and algorithm steps dispatch on its type via
  ``gossip.mix_stacked`` without knowing which transport is active.
  Schedules are periodic, so the ``rounds``-product starting at ``slot``
  only depends on ``slot % period`` — ``phi_for`` memoizes its wire
  representations in the per-run ``aux`` on that key, turning the per-step
  host work (matrix products, band decompositions) into a dict lookup after
  the first period,
* ``mix(aux, phi, tree)`` — the actual collective (what ``mix_stacked``
  dispatches to), exposed for direct use by trainers and tests,
* ``bytes_per_step(aux, phi, param_count)`` — wire-cost accounting, so
  communication plots can report BYTES moved, not just gossip rounds;
  ``bytes_per_link(aux, phi, param_count)`` refines the same accounting to
  a ``{(src, dst): bytes}`` map over directed node links (summing exactly
  to ``bytes_per_step``), feeding per-edge communication plots.

Registered backends (:data:`GOSSIP_BACKENDS`):

``dense``
    One ``(m, m)`` contraction per step.  Under GSPMD the einsum all-gathers
    all m stacked copies to every node — O(m) wire cost — but arbitrary
    multi-consensus products stay a single collective.
``banded``
    Cyclic-band decomposition (``BandedPhi``): each nonzero band is one
    cyclic shift, so ring / TDMA-matching schedules (degree <= 2) pay
    O(degree) collectives.  Single-device lowering is ``jnp.roll``.
``ppermute``
    The same bands lowered to ``lax.ppermute`` neighbor exchanges under
    ``shard_map`` on a node-axis device mesh (``PermutePhi``): each band is
    ONE collective-permute of the local shard, so the O(degree) win shows up
    in wire bytes on real hardware, not just host timings.
``compressed``
    Wraps ANY inner backend: payloads ride the inner wire format int-
    quantized with a CHOCO-style error-feedback residual
    (``core.compression``), cutting bytes by ``32 / bits``.  Stateful — the
    driven algorithm must thread a mix state (``Algorithm.init_mix_state``).

``"auto"`` (the ``runner.run`` default) picks by mesh availability first,
then schedule bandwidth: a node-axis mesh (axis of size m) -> ``ppermute``
— even for a dense-saturated offset union, since on a mesh every band is
one collective-permute of the local shard (all-gathering m stacked copies
would be strictly worse); no mesh + banded structure (offset union
strictly smaller than m) -> ``banded``; no mesh + saturated union (e.g.
faithful unbounded multi-consensus, whose k-round products acquire
bandwidth k) -> ``dense``.  On the auto path the old band-saturation
``RuntimeWarning`` is thus replaced by a silent correct choice; EXPLICITLY
requesting ``banded`` on a saturated schedule still warns (correct, but
strictly slower than dense).

Methods that quantize their own gossip payload declare it via
``AlgoMeta.compress_bits``; the runner wraps whatever transport resolves in
a :class:`CompressedBackend` at those bits, so the ``wire_bytes`` accounting
always reflects what actually moves.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compression, gossip, graphs, mesh as mesh_lib

__all__ = [
    "TransportMeta",
    "band_offset_union",
    "GossipBackend",
    "DenseBackend",
    "BandedBackend",
    "PPermuteBackend",
    "CompressedBackend",
    "GOSSIP_BACKENDS",
    "select_backend_name",
    "resolve_backend",
    "node_param_count",
    "mix_matrix",
    "batch_phis",
]

F32_BYTES = 4


# ---------------------------------------------------------------------------
# The static slice of AlgoMeta a transport needs
# ---------------------------------------------------------------------------

class TransportMeta(NamedTuple):
    """What ``prepare`` needs to know about the driven loop: which
    ``rounds`` values the gossip policy will request.  ``AlgoMeta`` is
    duck-compatible (the runner passes it directly); loops without an
    AlgoMeta (the LM trainer) build one via :meth:`constant`."""
    outer_lengths: tuple | None
    num_steps: int | None
    gossip_rounds: Callable[[int], int]

    @classmethod
    def constant(cls, rounds: int) -> "TransportMeta":
        """A fixed-rounds gossip policy (the LM trainer's shape).  One probe
        step suffices: the rounds-value set is the singleton {rounds}, so
        num_steps=1 keeps ``band_offset_union`` from walking a long loop."""
        return cls(None, 1, lambda k: rounds)


def _rounds_values(meta) -> list[int]:
    if meta.outer_lengths is not None:
        ks = range(1, max(meta.outer_lengths) + 1)
    else:
        ks = range(1, (meta.num_steps or 1) + 1)
    return sorted({meta.gossip_rounds(k) for k in ks})


def band_offset_union(schedule: graphs.MixingSchedule, meta) -> tuple:
    """The static band-offset union a compiled banded step must support:
    offsets of every `rounds`-product the schedule can produce, for every
    rounds value the gossip policy will request.  Early-exits once the union
    saturates at m offsets (no structure left to exploit).

    Computed on ``schedule.structure_schedule``: an aperiodic scenario
    wrapper only ever removes edges from its base schedule, and supports of
    products of nonnegative matrices are monotone in the factor supports, so
    the base schedule's (finitely enumerable) union is a valid superset for
    every degraded realization."""
    schedule = schedule.structure_schedule
    m = schedule.m
    offs: set = set()
    for rounds in _rounds_values(meta):
        offs.update(gossip.schedule_band_offsets(schedule, rounds))
        if len(offs) >= m:
            break
    return tuple(sorted(offs))


def _phi_key(schedule: graphs.MixingSchedule, slot: int, rounds: int):
    """Memoization key for a per-slot wire representation.

    Periodic schedules repeat every ``period`` slots, so steady-state steps
    hit the cache; aperiodic (scenario-degraded) schedules key on the
    absolute slot — every step's realized product is cached under its own
    key, which is still a win for repeated runs over the same aux."""
    if schedule.aperiodic:
        return (slot, rounds)
    return (slot % schedule.period, rounds)


def node_param_count(tree) -> int:
    """Per-node parameter count of a stacked pytree (leaves (m, ...))."""
    return sum(int(np.prod(leaf.shape[1:], dtype=np.int64))
               for leaf in jax.tree.leaves(tree))


def mix_matrix(phi):
    """Lower a wire representation to the dense (m, m) mixing matrix the
    fused resident-step kernel consumes, or ``None`` when no static
    single-device lowering exists.

    Trace-safe: called inside compiled chunk bodies on ``lax.scan``-sliced
    phis, so both branches of the return may be tracers.  ``None`` means
    the caller must keep the unfused step: ``PermutePhi`` mixes via mesh
    collectives (the stacked buffer never exists on one device), compressed
    and scenario wrappers thread mix state, and stateful-only phi types are
    rejected wholesale.
    """
    if isinstance(phi, gossip.BandedPhi):
        return gossip.banded_to_dense(phi.offsets, phi.coeffs)
    if isinstance(phi, gossip.PermutePhi):
        return None
    if isinstance(phi, compression.CompressedPhi):
        return None
    if gossip._STATEFUL_ONLY and isinstance(phi, gossip._STATEFUL_ONLY):
        return None
    # dense (m, m) arrays and their in-trace tracer slices
    if getattr(phi, "ndim", None) == 2:
        return jnp.asarray(phi, jnp.float32)
    return None


def batch_phis(phis: "list") -> Any:
    """Stack per-cell wire representations along a new leading CELL axis —
    the batched-sweep staging primitive (the runner's chunk stacking then
    prepends the time axis, giving (T, B, ...) phi leaves that a vmapped
    chunk executor slices per cell).

    Every phi must share its pytree STRUCTURE including static aux data
    (same ``BandedPhi`` offset union, same ``PermutePhi`` mesh/axis): the
    compiled step specializes on the aux, so cells gossiping over
    structurally different wire formats cannot ride one batched program —
    the clear error here is what the sweep driver surfaces for such ragged
    grids (use ``gossip="dense"``, whose (m, m) wire format is structure-
    free, to batch across arbitrary topologies).  Leaf dtypes are
    preserved (integer quantized payloads must not widen to f32)."""
    defs = {str(jax.tree.structure(p)) for p in phis}
    if len(defs) > 1:
        raise ValueError(
            f"cannot batch gossip wire representations with different "
            f"static structure across sweep cells: {sorted(defs)}; cells "
            f"whose schedules decompose into different band/permute "
            f"structures need gossip='dense' to share one batched program")
    return jax.tree.map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves]), *phis)


def _active_entries(offsets: tuple, coeffs, m: int) -> Iterator:
    """(band offset d, node i) pairs whose coefficient actually carries mass
    this step: node i receives ``x_{(i+d) mod m}`` with weight coeffs[b][i].

    Per-ENTRY (not whole-band) so links a failure model dropped at this step
    — whose Metropolis reweighting zeroes exactly those coefficients — are
    not charged."""
    c = np.asarray(coeffs)
    for b, d in enumerate(offsets):
        if d % m == 0:
            continue
        for i in np.flatnonzero(np.abs(c[b]) > 1e-12):
            yield d, int(i)


def _banded_wire_bytes(offsets: tuple, coeffs, m: int,
                       param_count: int) -> int:
    """Point-to-point accounting for band-structured gossip: each nonzero
    off-diagonal coefficient moves one param vector over one link."""
    n = sum(1 for _ in _active_entries(offsets, coeffs, m))
    return n * param_count * F32_BYTES


def _banded_link_bytes(offsets: tuple, coeffs, m: int,
                       param_count: int) -> dict:
    """Per-directed-link refinement of :func:`_banded_wire_bytes`: band
    ``d`` at node ``i`` means one param vector moves over the link
    ``(i+d) mod m -> i``."""
    links: dict = {}
    for d, i in _active_entries(offsets, coeffs, m):
        key = ((i + d) % m, i)
        links[key] = links.get(key, 0) + param_count * F32_BYTES
    return links


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class GossipBackend:
    """Protocol base.  Instances are stateless/reusable; all per-run state
    lives in the ``aux`` returned by :meth:`prepare`.  ``needs_mix_state``
    marks stateful transports (error feedback): the runner asks the driven
    algorithm to thread the state via ``Algorithm.init_mix_state``."""

    name: str = "?"
    needs_mix_state: bool = False

    def prepare(self, schedule: graphs.MixingSchedule, meta, *,
                mesh=None) -> Any:
        raise NotImplementedError

    def phi_for(self, aux, slot: int, rounds: int):
        """Host-side wire representation of the ``rounds``-product starting
        at schedule slot ``slot`` (a pytree; scan-stackable).  Memoized in
        ``aux`` on ``(slot % period, rounds)`` — products of a periodic
        schedule repeat, so steady-state steps cost a dict lookup."""
        raise NotImplementedError

    def mix(self, aux, phi, tree):
        """Apply one mixing — identical to ``gossip.mix_stacked(phi, tree)``
        for stateless backends (the dispatch algorithm steps rely on)."""
        return gossip.mix_stacked(phi, tree)

    def init_mix_state(self, aux, x0):
        """Per-run transport state threaded through the algorithm state
        (``needs_mix_state`` backends only).  ``x0`` is the stacked initial
        iterate — the state the first mix sees."""
        raise NotImplementedError(
            f"gossip backend {self.name!r} is stateless (needs_mix_state="
            f"{self.needs_mix_state})")

    def bytes_per_step(self, aux, phi, param_count: int) -> int:
        """Wire bytes this step's mix moves across node links."""
        raise NotImplementedError

    def bytes_per_link(self, aux, phi, param_count: int) -> dict:
        """``{(src, dst): bytes}`` over directed node links for this step's
        mix — the per-edge refinement of :meth:`bytes_per_step` (values sum
        exactly to it), for topology-aware communication plots."""
        raise NotImplementedError


class _DenseAux(NamedTuple):
    schedule: graphs.MixingSchedule
    m: int
    cache: dict


class DenseBackend(GossipBackend):
    """One pre-multiplied ``(m, m)`` contraction per step."""

    name = "dense"

    def prepare(self, schedule, meta, *, mesh=None):
        return _DenseAux(schedule, schedule.m, {})

    def phi_for(self, aux, slot, rounds):
        key = _phi_key(aux.schedule, slot, rounds)
        phi = aux.cache.get(key)
        if phi is None:
            phi = aux.cache[key] = aux.schedule.consensus_rounds(slot, rounds)
        return phi

    def bytes_per_step(self, aux, phi, param_count):
        # the dense einsum lowers to an all-gather of the full stacked
        # buffer: every node receives the other m - 1 copies, regardless of
        # the product's sparsity
        return aux.m * (aux.m - 1) * param_count * F32_BYTES

    def bytes_per_link(self, aux, phi, param_count):
        return {(j, i): param_count * F32_BYTES
                for i in range(aux.m) for j in range(aux.m) if i != j}


class _BandedAux(NamedTuple):
    schedule: graphs.MixingSchedule
    m: int
    offsets: tuple
    cache: dict


class BandedBackend(GossipBackend):
    """Cyclic-band decomposition on the schedule's static offset union."""

    name = "banded"

    def prepare(self, schedule, meta, *, mesh=None):
        offsets = band_offset_union(schedule, meta)
        if len(offsets) >= schedule.m:
            # only reachable when banded was requested EXPLICITLY ("auto"
            # picks dense on a saturated union): still correct, but m
            # cyclic passes per step are strictly slower than one (m, m)
            # contraction
            warnings.warn(
                f"{schedule.name}: banded gossip needs all {len(offsets)} "
                f"of {schedule.m} band offsets — no O(degree) structure to "
                f"exploit; gossip='auto' or 'dense' will be faster (cap "
                f"multi-consensus rounds, e.g. k_max, to keep products "
                f"banded)", RuntimeWarning, stacklevel=3)
        return _BandedAux(schedule, schedule.m, offsets, {})

    def phi_for(self, aux, slot, rounds):
        key = _phi_key(aux.schedule, slot, rounds)
        phi = aux.cache.get(key)
        if phi is None:
            phi = aux.cache[key] = gossip.BandedPhi.from_dense(
                aux.schedule.consensus_rounds(slot, rounds), aux.offsets)
        return phi

    def bytes_per_step(self, aux, phi, param_count):
        return _banded_wire_bytes(phi.offsets, phi.coeffs, aux.m, param_count)

    def bytes_per_link(self, aux, phi, param_count):
        return _banded_link_bytes(phi.offsets, phi.coeffs, aux.m, param_count)


class _PermuteAux(NamedTuple):
    schedule: graphs.MixingSchedule
    m: int
    offsets: tuple
    mesh: Any
    axis: str
    cache: dict


def _node_axis(mesh, m: int) -> str | None:
    """The mesh axis carrying one node per device, if any."""
    for axis, size in mesh.shape.items():
        if size == m:
            return axis
    return None


class PPermuteBackend(GossipBackend):
    """Banded gossip lowered to ``lax.ppermute`` under ``shard_map``.

    Needs a mesh with a node axis of size m (one node per device along that
    axis).  When ``mesh`` is None, builds a 1-D ``("nodes",)`` mesh over the
    first m local devices — raising with an ``XLA_FLAGS`` hint when the
    process has fewer.
    """

    name = "ppermute"

    def prepare(self, schedule, meta, *, mesh=None):
        m = schedule.m
        if mesh is None:
            ndev = len(jax.devices())
            if ndev < m:
                raise ValueError(
                    f"ppermute gossip needs a mesh with a node axis of size "
                    f"{m}, but only {ndev} device(s) are visible "
                    f"(force a host-platform mesh with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={m}, or pass "
                    f"mesh=)")
            mesh = mesh_lib.make_mesh((m,), ("nodes",))
            axis = "nodes"
        else:
            axis = _node_axis(mesh, m)
            if axis is None:
                raise ValueError(
                    f"mesh {dict(mesh.shape)} has no axis of size m={m} to "
                    f"carry the node dimension")
        return _PermuteAux(schedule, m, band_offset_union(schedule, meta),
                           mesh, axis, {})

    def phi_for(self, aux, slot, rounds):
        key = _phi_key(aux.schedule, slot, rounds)
        phi = aux.cache.get(key)
        if phi is None:
            phi = aux.cache[key] = gossip.PermutePhi.from_dense(
                aux.schedule.consensus_rounds(slot, rounds), aux.offsets,
                aux.mesh, aux.axis)
        return phi

    def bytes_per_step(self, aux, phi, param_count):
        return _banded_wire_bytes(phi.offsets, phi.coeffs, aux.m, param_count)

    def bytes_per_link(self, aux, phi, param_count):
        return _banded_link_bytes(phi.offsets, phi.coeffs, aux.m, param_count)


class _CompressedAux(NamedTuple):
    inner_backend: GossipBackend
    inner_aux: Any
    bits: int


@dataclasses.dataclass(frozen=True)
class CompressedBackend(GossipBackend):
    """Error-feedback quantized gossip over ANY inner wire format.

    ``inner`` names (or is) the transport the quantized payload rides on;
    ``bits`` the integer width.  Stateful: the residual accumulator threads
    through the algorithm state (``Algorithm.init_mix_state``), so only
    algorithms that support a mix state (DPSVRG, GT-SVRG, loopless DPSVRG)
    can be driven compressed.
    """

    inner: Any = "dense"   # str name or GossipBackend instance
    bits: int = 8

    name = "compressed"
    needs_mix_state = True

    def _inner_backend(self) -> GossipBackend:
        if isinstance(self.inner, str):
            if self.inner == "compressed":
                raise ValueError("compressed cannot wrap itself")
            return GOSSIP_BACKENDS[self.inner]
        return self.inner

    def prepare(self, schedule, meta, *, mesh=None):
        ib = self._inner_backend()
        return _CompressedAux(ib, ib.prepare(schedule, meta, mesh=mesh),
                              self.bits)

    def phi_for(self, aux, slot, rounds):
        return compression.CompressedPhi(
            aux.inner_backend.phi_for(aux.inner_aux, slot, rounds), aux.bits)

    def init_mix_state(self, aux, x0) -> compression.CompressionState:
        return compression.init_state(x0)

    def mix(self, aux, phi, tree, mix_state=None):
        """Stateful mix: returns ``(mixed, new_state)``."""
        if mix_state is None:
            raise ValueError("compressed gossip needs an error-feedback "
                             "state (see compression.init_state)")
        return compression.mix_with_state(phi, tree, mix_state)

    def bytes_per_step(self, aux, phi, param_count):
        inner = aux.inner_backend.bytes_per_step(aux.inner_aux, phi.inner,
                                                 param_count)
        return inner * aux.bits // 32

    def bytes_per_link(self, aux, phi, param_count):
        # per-link floors can undershoot the single-floor total
        # (bytes_per_step) when bits doesn't divide 32 evenly; distribute
        # the rounding remainder deterministically so the map still sums
        # EXACTLY to bytes_per_step (the documented invariant)
        inner = aux.inner_backend.bytes_per_link(aux.inner_aux, phi.inner,
                                                 param_count)
        links = {link: b * aux.bits // 32 for link, b in inner.items()}
        remainder = (self.bytes_per_step(aux, phi, param_count)
                     - sum(links.values()))
        for link in sorted(links):
            if remainder <= 0:
                break
            links[link] += 1
            remainder -= 1
        return links


# ---------------------------------------------------------------------------
# Registry + "auto" selection
# ---------------------------------------------------------------------------

GOSSIP_BACKENDS: dict[str, GossipBackend] = {
    "dense": DenseBackend(),
    "banded": BandedBackend(),
    "ppermute": PPermuteBackend(),
    "compressed": CompressedBackend(),
}


def select_backend_name(schedule: graphs.MixingSchedule, meta,
                        mesh=None) -> str:
    """The ``"auto"`` rule.

    A node-axis mesh (an axis of size m) wins outright -> ``"ppermute"``:
    on a real mesh every band is one collective-permute of the LOCAL shard
    regardless of how many bands the union holds, so even a dense-saturated
    union (which historically forced ``"dense"`` and silently ignored the
    mesh) moves O(m) local payloads per step instead of all-gathering m
    stacked copies to every node.  Otherwise: banded structure present
    (static offset union strictly smaller than m) -> ``"banded"``; saturated
    union (e.g. faithful DPSVRG multi-consensus, whose unbounded k-round
    products acquire bandwidth k) -> ``"dense"``: m cyclic passes per step
    on ONE device would be strictly slower than one (m, m) contraction, so
    the old band-saturation ``RuntimeWarning`` is now just the dense choice.
    """
    if mesh is not None and _node_axis(mesh, schedule.m) is not None:
        return "ppermute"
    if len(band_offset_union(schedule, meta)) >= schedule.m:
        return "dense"
    return "banded"


def resolve_backend(gossip, schedule: graphs.MixingSchedule, meta,
                    mesh=None) -> GossipBackend:
    """``gossip`` is a registry name, ``"auto"``, or a backend instance."""
    if isinstance(gossip, str):
        name = (select_backend_name(schedule, meta, mesh)
                if gossip == "auto" else gossip)
        try:
            return GOSSIP_BACKENDS[name]
        except KeyError:
            raise ValueError(
                f"unknown gossip backend {gossip!r}: expected 'auto', one of "
                f"{sorted(GOSSIP_BACKENDS)}, or a GossipBackend instance"
            ) from None
    return gossip
