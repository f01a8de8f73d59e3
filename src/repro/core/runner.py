"""The single generic driver for every decentralized algorithm.

``run(algo, problem, schedule, ...)`` owns what the five historical ``*_run``
loops each re-implemented: per-node minibatch sampling, time-varying
gossip-matrix scheduling (multi-consensus products off the schedule's slot
stream), epoch / communication accounting, metric recording with pluggable
extra recorders, and outer-round orchestration.  Algorithms only supply the
:class:`~repro.core.algorithm.Algorithm` state/step/outer triple plus
declarative metadata.

Three execution paths:

* **host loop** (default): one device dispatch per inner step, iterating the
  algorithm's ``step`` exactly like the historical loops — bit-for-bit
  reproducible against them at a fixed seed (tests/test_algorithm_api.py).
* **``lax.scan`` fast path** (``scan=True``): between two metric records the
  driver pre-samples the chunk of minibatches, pre-stacks the chunk's gossip
  matrices and step sizes, and executes the whole chunk in ONE compiled
  device dispatch — removing per-step Python/dispatch overhead from the hot
  path.  Host-side rng draws happen in the same order as the host loop, so
  both paths consume identical batches; results agree to float tolerance
  (XLA may fuse the scanned body differently).
* **device-resident path** (``resident=True``): the scan path still pays a
  host<->device round trip per chunk (ship the stacked minibatch tree in,
  pull metrics out at each record).  The resident path removes that seam:
  the run is PLANNED on host first (chunk schedule, gossip products, step
  sizes, minibatch indices — all data-independent), staged to the device in
  ONE ``jax.device_put``, executed chunk-by-chunk with DONATED carries (XLA
  updates the stacked iterate in place instead of copying the (m, d)
  buffers), and metrics are recorded by a jitted on-device kernel into
  preallocated buffers (objective via the vmap'd loss + prox, consensus via
  ``jnp`` norms) that are pulled to host ONCE at run end — O(1) transfers
  per run instead of two per chunk.  ``sampling="host"`` (default) draws
  minibatch indices from the same ``np.random`` stream as the other paths
  (histories agree to float tolerance); ``sampling="device"`` instead
  threads a ``jax.random`` key through the scan carry and gathers
  minibatches inside the compiled body — a different (but equally valid)
  sample stream, and nothing per-step ever leaves the device.
  ``RunResult.extras['transfers_h2d'/'transfers_d2h']`` reports the
  driver-initiated transfer events for every path, and
  ``extras['bytes_h2d'/'bytes_d2h']`` the bytes they moved.  A resident
  job marks its host phases (plan, stage, dispatch, pull) and its compiled
  chunks their device work with the names of :mod:`repro.core.spans`.

Gossip transports are pluggable (``gossip``, a :mod:`repro.core.transport`
backend name or instance; default ``"auto"``):

* ``"dense"`` / ``"banded"`` / ``"ppermute"`` / ``"compressed"`` — see
  :data:`~repro.core.transport.GOSSIP_BACKENDS`.  The resolved backend does
  its static precompute once (``prepare``), emits a host-side wire
  representation per step (``phi_for``) that the driver feeds through the
  step (and through the scan ``xs`` — every representation is a pytree, so
  stacking is generic), and accounts wire bytes (``bytes_per_step``), which
  the driver accumulates into the ``wire_bytes`` extras column.
* ``"auto"`` picks by schedule bandwidth and mesh availability
  (:func:`~repro.core.transport.select_backend_name`): banded structure ->
  ``banded`` (or ``ppermute`` when ``mesh`` is given), saturated band union
  (e.g. faithful unbounded multi-consensus) -> ``dense``.  Histories agree
  across backends to float tolerance; ``"dense"`` reproduces the historical
  loops bit-for-bit.
* stateful transports (``compressed``) additionally require the algorithm
  to thread a mix state (``Algorithm.init_mix_state``).

Every execution choice above is carried by ONE immutable value — an
:class:`~repro.core.exec_spec.ExecSpec` passed as ``run``'s fourth argument
(``runner.run(algo, problem, sched, ExecSpec(resident=True, ...))``).  The
historical per-keyword spellings (``scan=``, ``resident=``, ``sampling=``,
``device_transitions=``, ``kernel=``, ``gossip=``, ``mesh=``) still work
for one release through a ``DeprecationWarning`` shim (like the retired
``gossip_mode=`` keyword, which still maps onto the spec's ``gossip``
field); passing both a spec and a legacy keyword raises.

``ExecSpec(shard="nodes")`` additionally partitions the resident path's
stacked ``(m, d)`` node axis over a device mesh via GSPMD: the staged
inputs, dataset, and donated state carry are placed with a
``NamedSharding`` splitting axis ``m`` (the caller's ``mesh``, else the
mesh the ``ppermute`` transport already built, else a fresh 1-D mesh over
every visible device — the axis size must divide ``m``), and the SAME
compiled chunk executors then run SPMD with each device owning a block of
simulated nodes — m >> core-count networks in one launch, histories equal
to the unsharded run to float tolerance, transfer ledger still O(1), and
error-feedback compression state shard-local.

Scan chunks of distinct lengths are padded to a small set of bucket lengths
(next power of two; the steady-state ``record_every`` chunk stays exact) with
a per-step keep-mask, so e.g. DPSVRG's growing ``K_s`` rounds compile
O(log max K_s) scan executables instead of one per distinct round length.
Padded steps are skipped at runtime via ``lax.cond`` and consume no rng
draws, so histories are unchanged.  ``scan_executable_count`` exposes the
compiled-variant count for benchmarks and tests.

Compiled chunk executors are PERSISTENT across ``run()`` calls and across
Algorithm instances: executors are cached by (algorithm name, path kind,
sampling mode, step identity), and step identity is stable across rebuilt
instances with identical loss/prox closures (``algorithm._shared_step``),
so a sweep that reconstructs the algorithm per (topology, seed, ...) point
compiles each (bucket, backend, m, d) chunk variant ONCE — the per-shape
specialization lives in each executor's own ``jax.jit`` cache.  Use
``reset_executable_caches()`` to measure true cold starts.

The terminal record is deduplicated: the historical DPSVRG loop appended a
final history point even when the last inner step had just been recorded,
duplicating the last row whenever ``K_S % record_every == 0``.  The unified
recorder only emits the terminal point if the last step wasn't recorded.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import inspect
import warnings
import weakref
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import (algorithm as algorithm_lib, exec_spec as exec_spec_lib,
               gossip, graphs, mesh as mesh_lib, spans, transport)
from .exec_spec import UNSET, ExecSpec

__all__ = ["RunHistory", "RunResult", "Recorder", "run", "run_sweep",
           "SweepResult", "ExecSpec", "sample_batch",
           "scan_executable_count", "reset_executable_caches",
           "traceable_consensus"]


class RunHistory(NamedTuple):
    objective: np.ndarray          # F(x_bar) per recorded point
    consensus: np.ndarray          # mean ||x_i - x_bar||
    epochs: np.ndarray             # effective dataset passes at each point
    comm_rounds: np.ndarray        # cumulative gossip rounds
    steps: np.ndarray              # cumulative inner steps


class RunResult(NamedTuple):
    params: Any                    # final stacked iterate
    history: RunHistory
    extras: dict                   # name -> np.ndarray from extra recorders


def sample_batch(rng: np.random.Generator, data, batch_size: int):
    """Sample per-node minibatch indices and gather. data leaves: (m, n, ...)."""
    first = jax.tree.leaves(data)[0]
    m, n = first.shape[0], first.shape[1]
    idx = rng.integers(0, n, size=(m, batch_size))
    return jax.tree.map(lambda a: np.take_along_axis(
        a, idx.reshape(m, batch_size, *([1] * (a.ndim - 2))), axis=1), data)


def objective_value(loss_fn, prox, params, full_data) -> float:
    """F(x_bar) = (1/m) sum_i f_i(x_bar) + h(x_bar)."""
    xbar = gossip.node_mean(params)
    m = jax.tree.leaves(params)[0].shape[0]
    xbar_st = gossip.stack_tree(xbar, m)
    losses = jax.vmap(loss_fn)(xbar_st, full_data)
    return float(jnp.mean(losses) + prox.value(xbar))


class Recorder:
    """Accumulates the RunHistory columns under the algorithm's metric
    conventions, plus arbitrary extra metrics ``name -> fn(params) -> float``
    and the driver-supplied ``wire_bytes`` column (cumulative gossip bytes
    from the transport backend's accounting).
    """

    def __init__(self, objective_fn: Callable, meta, m: int, n: int,
                 extra_metrics: dict | None = None):
        self._obj = objective_fn
        self._meta = meta
        self._m, self._n = m, n
        self._extra = extra_metrics or {}
        self._cols = {k: [] for k in RunHistory._fields}
        self._extras = {k: [] for k in self._extra}
        self._wire: list = []

    def record(self, params, *, t: int, grad_evals: int, comm_rounds: int,
               wire_bytes: int = 0):
        meta = self._meta
        self._wire.append(wire_bytes)
        self._cols["objective"].append(self._obj(params))
        if meta.track_consensus:
            cons = graphs.consensus_distance(np.stack(
                [np.concatenate([np.ravel(l[i])
                                 for l in jax.tree.leaves(params)])
                 for i in range(self._m)]))
        else:
            cons = 0.0
        self._cols["consensus"].append(cons)
        self._cols["epochs"].append(
            grad_evals / float(self._m * self._n)
            if meta.epoch_metric == "grad" else float(t))
        self._cols["comm_rounds"].append(
            comm_rounds if meta.comm_metric == "gossip" else t)
        self._cols["steps"].append(t)
        for name, fn in self._extra.items():
            self._extras[name].append(fn(params))

    def history(self) -> RunHistory:
        return RunHistory(**{k: np.array(v) for k, v in self._cols.items()})

    def extras(self) -> dict:
        out = {k: np.array(v) for k, v in self._extras.items()}
        out["wire_bytes"] = np.array(self._wire, dtype=np.int64)
        return out


# ---------------------------------------------------------------------------
# Persistent executable cache
# ---------------------------------------------------------------------------
#
# Compiled chunk executors / record kernels survive across run() calls AND
# across Algorithm instances.  Keys embed the function identities an executor
# closes over (the step fn, the loss/prox of the record kernel), which
# ``algorithm._shared_step`` keeps stable for rebuilt instances with the same
# closures — so the cache can never serve a stale computation, and a sweep
# that reconstructs its Algorithm per point reuses every compiled
# (bucket, backend, m, d) chunk variant from each executor's jax.jit cache.

_EXEC_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()
_EXEC_CACHE_MAX = 64

# algo instance -> its scan executor, for scan_executable_count introspection
_SCAN_EXEC_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _shared_exec(key: tuple, make: Callable[[], Callable]) -> Callable:
    return algorithm_lib.memoize_into(_EXEC_CACHE, _EXEC_CACHE_MAX, key,
                                      make)


def reset_executable_caches() -> None:
    """Drop every persistent executor/step cache (true cold-start
    measuring).  Covers the scan and resident chunk executors, the on-device
    record kernels, the vmapped batched-sweep executors (``core.sweep``
    routes them through the same cache), and the shared step cache."""
    _EXEC_CACHE.clear()
    _SCAN_EXEC_CACHE.clear()
    algorithm_lib._SHARED_STEPS.clear()
    from . import sweep as sweep_lib
    sweep_lib._SWEEP_EXEC_CACHE.clear()


def _make_scan_exec(algo):
    """One compiled dispatch executing a whole (possibly padded) chunk."""
    cached = _SCAN_EXEC_CACHE.get(algo)
    if cached is not None:
        return cached
    # close over the step function only, NOT the Algorithm: a cached value
    # referencing the weak key would pin every Algorithm (and its closed-over
    # dataset) forever
    step_fn = algo.step
    has_batch = algo.meta.batch_size > 0

    def make():
        def body(state, xs):
            if has_batch:
                batch, phi, alpha, keep = xs
            else:
                phi, alpha, keep = xs
            # padded steps (keep=False) skip the update entirely at runtime,
            # so bucketed chunks stay numerically identical to unpadded ones
            new_state = jax.lax.cond(
                keep,
                lambda s: step_fn(s, batch if has_batch else None, phi,
                                  alpha),
                lambda s: s,
                state)
            return new_state, None

        @jax.jit
        def exec_chunk(state, xs):
            return jax.lax.scan(body, state, xs)[0]

        return exec_chunk

    exec_chunk = _shared_exec(("scan", algo.meta.name, has_batch, step_fn),
                              make)
    _SCAN_EXEC_CACHE[algo] = exec_chunk
    return exec_chunk


def scan_executable_count(algo) -> int:
    """Number of scan-chunk variants compiled for ``algo``'s executor so far
    (0 if the scan path never ran).  Chunk-length bucketing keeps this
    O(#buckets) instead of O(#distinct chunk lengths).  The executor is
    SHARED across Algorithm instances with the same step closures (the
    persistent executable cache), so counts accumulate across runs/instances
    — compare before/after deltas to measure a single run.  Returns -1 when
    the running jax no longer exposes the jit cache-size introspection (it
    is a private API); callers must treat -1 as "unknown", not a count."""
    exec_chunk = _SCAN_EXEC_CACHE.get(algo)
    if exec_chunk is None:
        # link (or reuse) the shared executor so before/after deltas work
        # even when the caller asks before the first scan run
        exec_chunk = _make_scan_exec(algo)
    cache_size = getattr(exec_chunk, "_cache_size", None)
    if cache_size is None:
        return -1
    return cache_size()


def _bucket_length(chunk: int, record_every: int) -> int:
    """Pad-to-bucket policy: the steady-state chunk (== record_every) keeps
    its exact length; every other length rounds up to the next power of two,
    bounding compiled scan variants at O(log max-chunk) + 1."""
    if record_every and chunk == record_every:
        return chunk
    return 1 << max(chunk - 1, 0).bit_length()


def _stack_wire(leaves):
    """Stack per-step wire leaves, canonicalizing floats to f32 but KEEPING
    integer payload dtypes (e.g. an 8-bit quantized transport's payload must
    not silently widen to f32 on the wire — the historical force-cast here
    quadrupled what the xs stacking shipped for int8 leaves)."""
    out = np.stack([np.asarray(l) for l in leaves])
    if np.issubdtype(out.dtype, np.floating):
        return out.astype(np.float32, copy=False)
    return out


def _stack_phis(phis):
    """Stack host-side per-step wire representations into scan xs.  Every
    transport's phi is a pytree (dense array, BandedPhi, PermutePhi,
    CompressedPhi, ...) whose static parts are aux data, so one generic
    dtype-preserving leaf-stack covers all backends."""
    return jax.tree.map(lambda *leaves: jnp.asarray(_stack_wire(leaves)),
                        *phis)


def _stack_inputs(meta, batches, phis, alphas, keep):
    phis = _stack_phis(phis)
    alphas = jnp.asarray(np.array(alphas, np.float32))
    keep = jnp.asarray(np.array(keep, np.bool_))
    if meta.batch_size > 0:
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
        return (batch, phis, alphas, keep)
    return (phis, alphas, keep)


# ---------------------------------------------------------------------------
# Device-resident path: plan on host, stage once, execute on device,
# pull the history once
# ---------------------------------------------------------------------------

# Test hook: the resident driver wraps every chunk/record DISPATCH in this
# context.  Swapping in ``lambda: jax.transfer_guard("disallow")`` makes XLA
# itself fault on any host<->device transfer during the compiled hot path —
# the strongest form of the O(1)-transfers claim.
_RESIDENT_DISPATCH_GUARD: Callable = contextlib.nullcontext


def _flatten_nodes(params) -> jnp.ndarray:
    """(m, total_d) view of a stacked pytree."""
    return jnp.concatenate(
        [l.reshape(l.shape[0], -1) for l in jax.tree.leaves(params)], axis=1)


def traceable_consensus(params) -> jnp.ndarray:
    """mean_i ||x_i - x_bar|| as a jittable kernel — the in-graph
    replacement for the Recorder's per-node host ravel/concatenate loop."""
    flat = _flatten_nodes(params)
    xbar = jnp.mean(flat, axis=0, keepdims=True)
    return jnp.mean(jnp.linalg.norm(flat - xbar, axis=1))


def _resolved_objective(meta, problem):
    """The traceable recorded objective ``obj(stacked_params, data)`` for
    the on-device record kernels (single-run AND batched sweep), resolved
    in order: ``meta.resident_objective`` (the AlgoMeta traceable
    contract) -> ``problem.objective_fn`` (must then be traceable) -> the
    default composite F(x̄) via the vmap'd loss + prox value."""
    if meta.resident_objective is not None:
        return meta.resident_objective
    if problem.objective_fn is not None:
        host_obj = problem.objective_fn

        def obj(params, data):
            del data
            return host_obj(params)

        return obj
    loss_fn, prox = problem.loss_fn, problem.prox

    def obj(params, data):
        xbar = gossip.node_mean(params)
        m = jax.tree.leaves(params)[0].shape[0]
        losses = jax.vmap(loss_fn)(gossip.stack_tree(xbar, m), data)
        return jnp.mean(losses) + prox.value(xbar)

    return obj


def _make_record_kernel(problem, meta):
    """Jitted on-device metric recorder: computes the objective (and
    consensus when tracked) from the live state and writes them into the
    preallocated history buffers at the carried record slot.  Buffers are
    DONATED, so the update is in place.  The objective comes from
    :func:`_resolved_objective`."""
    def make():
        obj = _resolved_objective(meta, problem)
        track = meta.track_consensus

        @functools.partial(jax.jit, donate_argnums=0)
        def record(bufs, params, data):
            obj_buf, cons_buf, slot = bufs
            with spans.scope(spans.RECORD):
                obj_buf = obj_buf.at[slot].set(obj(params, data))
                if track:
                    cons_buf = cons_buf.at[slot].set(
                        traceable_consensus(params))
            return (obj_buf, cons_buf, slot + 1)

        return record

    return _shared_exec(
        ("record", meta.name, meta.track_consensus, problem.loss_fn,
         problem.prox, problem.objective_fn, meta.resident_objective), make)


def _resolve_transitions(algo, device_transitions) -> bool:
    """Whether the resident path folds ``outer``/``end_outer`` into the
    compiled chunks (``lax.cond`` on the precomputed round schedule) instead
    of dispatching them from host between chunks.  ``"auto"`` uses the
    traceable contract whenever the algorithm declares it; ``True``
    requires it; ``False`` keeps the host dispatches."""
    meta = algo.meta
    needs_outer = (meta.outer_lengths is not None
                   or meta.snapshot_prob is not None)
    if not needs_outer:
        return False                # nothing to fold; plain chunks already
    needs_end = meta.outer_lengths is not None and algo.end_outer is not None
    has = (algo.outer is None or algo.outer_traced is not None) and \
        (not needs_end or algo.end_outer_traced is not None)
    if device_transitions == "auto":
        return has
    if device_transitions and not has:
        raise ValueError(
            f"{meta.name}: device_transitions=True needs the traceable "
            f"outer-transition contract (Algorithm.outer_traced"
            f"{' + end_outer_traced' if needs_end else ''}); this algorithm "
            f"does not declare it")
    return bool(device_transitions)


def _chunk_body(data, *, step_fn, meta, device_sampling: bool,
                transitions: bool, outer_fn=None, end_fn=None,
                has_opre: bool = False, has_opost: bool = False,
                has_end: bool = False):
    """The ONE scan body both resident executors compile: the single-run
    chunk executor uses it directly; the batched sweep executor builds it
    per cell (inside ``vmap``, with the cell's traced step/transition
    functions) — so a semantics fix here reaches both paths."""
    has_batch = meta.batch_size > 0
    bsz = meta.batch_size
    if device_sampling:
        first = jax.tree.leaves(data)[0]
        m, n = first.shape[0], first.shape[1]

        def sample(key):
            with spans.scope(spans.SAMPLE):
                idx = jax.random.randint(key, (m, bsz), 0, n)
                return jax.tree.map(
                    lambda a: jnp.take_along_axis(
                        a, idx.reshape(m, bsz, *([1] * (a.ndim - 2))),
                        axis=1), data)

    def apply_step(carry, batch, phi, alpha, keep):
        # padded steps (keep=False) skip the update entirely at runtime,
        # so bucketed chunks stay numerically identical to unpadded ones
        # (and consume no device-side rng draws)
        if device_sampling:
            def do(operand):
                state, key = operand
                key, sub = jax.random.split(key)
                return step_fn(state, sample(sub), phi, alpha), key

            return jax.lax.cond(keep, do, lambda o: o, carry)
        return jax.lax.cond(
            keep,
            lambda s: step_fn(s, batch, phi, alpha),
            lambda s: s, carry)

    def cond_state(pred, fn, carry):
        # transitions act on the algorithm state, not the rng key
        if device_sampling:
            state, key = carry
            return (jax.lax.cond(pred, fn, lambda s: s, state), key)
        return jax.lax.cond(pred, fn, lambda s: s, carry)

    def snapshot(pred, carry):
        with spans.scope(spans.SNAPSHOT):
            return cond_state(pred, lambda s: outer_fn(s, data), carry)

    def body(carry, xs):
        if transitions:
            if has_batch and not device_sampling:
                batch, phi, alpha, keep, o_pre, o_post, e_post, e_k = xs
            else:
                phi, alpha, keep, o_pre, o_post, e_post, e_k = xs
                batch = None
        else:
            if has_batch and not device_sampling:
                batch, phi, alpha, keep = xs
            else:
                phi, alpha, keep = xs
                batch = None
        if has_opre:
            carry = snapshot(o_pre, carry)
        carry = apply_step(carry, batch, phi, alpha, keep)
        if has_opost:
            carry = snapshot(o_post, carry)
        if has_end:
            carry = cond_state(e_post, lambda s: end_fn(s, e_k), carry)
        return carry, None

    return body


def _resolve_kernel_step(algo, kernel: str):
    """The chunk body's step for a ``kernel=`` mode: the algorithm's fused
    twin (``AlgoMeta.fused_step``) for "pallas"/"auto" when the method
    declares one, else the plain step.  The twin itself falls back to the
    unfused body at trace time for configurations with no fused lowering,
    so this resolution only decides WHICH step identity keys the executor
    cache."""
    if kernel != "xla" and algo.meta.fused_step is not None:
        return algo.meta.fused_step(kernel)
    return algo.step


def _make_resident_exec(algo, sampling: str, transitions: bool = False,
                        kernel: str = "xla"):
    """Compiled chunk executor for the resident path.  The carried state is
    DONATED (XLA updates the stacked iterate in place — no (m, d) copy per
    chunk); with ``sampling="device"`` the carry additionally threads a
    ``jax.random`` key and minibatches are gathered from the device-resident
    dataset inside the scan body, so the chunk's xs carry no batch tree at
    all.  With ``transitions=True`` the xs additionally carry per-step
    outer-transition flags (outer-before, outer-after for coin-flip
    snapshots, end-of-round + its K) and the body applies the algorithm's
    TRACED transitions under ``lax.cond`` — no host dispatch per round.
    ``kernel`` swaps the fused resident-step body in (see
    :func:`_resolve_kernel_step`); the executor-cache key structure is
    unchanged — the fused step rides the step-identity slot."""
    step_fn = _resolve_kernel_step(algo, kernel)
    meta = algo.meta
    has_batch = meta.batch_size > 0
    bsz = meta.batch_size
    device_sampling = has_batch and sampling == "device"
    outer_fn = algo.outer_traced if transitions else None
    end_fn = algo.end_outer_traced if transitions else None
    has_opre = (transitions and meta.outer_lengths is not None
                and outer_fn is not None)
    has_opost = (transitions and meta.snapshot_prob is not None
                 and outer_fn is not None)
    has_end = (transitions and meta.outer_lengths is not None
               and end_fn is not None and algo.end_outer is not None)

    def make():
        @functools.partial(jax.jit, donate_argnums=0)
        def exec_chunk(carry, xs, data):
            body = _chunk_body(
                data, step_fn=step_fn, meta=meta,
                device_sampling=device_sampling, transitions=transitions,
                outer_fn=outer_fn, end_fn=end_fn, has_opre=has_opre,
                has_opost=has_opost, has_end=has_end)
            return jax.lax.scan(body, carry, xs)[0]

        return exec_chunk

    return _shared_exec(
        ("resident", meta.name, has_batch, sampling, bsz, step_fn,
         transitions, outer_fn, end_fn), make)


def _unalias_for_donation(tree):
    """Copy duplicate leaves so the donated carry never hands XLA the same
    buffer twice (``Attempt to donate the same buffer twice``): algorithm
    transitions alias freely — e.g. DPSVRG's ``outer`` sets ``est.snapshot``
    to the live ``anchor``, GT-SVRG's init points tracker/v_prev at the x0
    full gradient.  Device-side copies only; no host transfer."""
    seen: set = set()

    def dedupe(leaf):
        if id(leaf) in seen:
            return jnp.array(leaf, copy=True)
        seen.add(id(leaf))
        return leaf

    return jax.tree.map(dedupe, tree)


def _shield_for_donation(tree):
    """Fresh device copies of EVERY leaf: the initial state references
    caller-owned buffers (``problem.x0``, dataset-derived full gradients)
    that a donated call would invalidate for every later run."""
    return jax.tree.map(lambda a: jnp.array(a, copy=True), tree)


class _Chunk(NamedTuple):
    xs: Any                        # host-side stacked xs for one chunk


class _Plan(NamedTuple):
    ops: list                      # ("chunk", i) | ("outer",) |
    #                                ("end_outer", K) | ("record",)
    chunks: list
    cols: dict                     # host-computable history columns
    wire: np.ndarray               # cumulative wire bytes per record
    num_records: int
    phi_batched: bool = False      # batched plans: phis carry a cell axis
    opost_batched: bool = False    # batched plans: coin flips per cell


class _PlanCell(NamedTuple):
    """One sweep cell's planning inputs.  The single-run resident path is
    the one-cell special case."""
    meta: Any
    rng: Any
    backend: Any
    aux: Any


def _plan_resident(cells: "list[_PlanCell]", *, m: int, n: int,
                   param_count: int, record_every: int, sampling: str,
                   host_data, transitions: bool = False,
                   batched: bool = False) -> _Plan:
    """Walk the run's (data-independent) control flow WITHOUT touching the
    device: chunk boundaries, bucket padding, gossip products, step sizes,
    minibatch indices (``sampling="host"``: same ``np.random`` draw order as
    the host/scan paths — per step, batch indices then the loopless coin
    flip), and every host-computable history column.  The result is staged
    in one transfer and executed without further host involvement.

    ``cells`` is one entry per sweep cell (cell metas must agree on loop
    STRUCTURE — the sweep driver validates; numeric values like step sizes,
    rng streams, and snapshot probabilities vary per cell).  With
    ``batched=True`` the chunk xs grow a cell axis (batches/alphas at axis
    1, phis only when cells gossip over distinct schedules) and the
    per-cell history columns stack to (records, cells).  With
    ``transitions=True`` the plan contains NO host ``outer``/``end_outer``
    ops: per-step flags in the xs drive the algorithm's traced transitions
    inside the compiled chunk (``lax.cond`` on this precomputed round
    schedule) — required for batched plans, optional for single runs."""
    meta0 = cells[0].meta
    B = len(cells)
    if batched and not transitions:
        raise ValueError("batched plans fold outer transitions into the "
                         "compiled chunks; transitions=False only supports "
                         "a single cell")
    has_batch = meta0.batch_size > 0
    host_sampling = has_batch and sampling == "host"
    bsz = meta0.batch_size
    has_snapshot = meta0.snapshot_prob is not None
    opost_batched = batched and has_snapshot
    multi_aux = len({id(c.aux) for c in cells}) > 1
    phi_batched = batched and multi_aux

    ops: list = []
    chunks: list = []
    cols = {"epochs": [], "comm_rounds": [], "steps": []}
    wire_col: list = []

    grad_evals = [m * n if c.meta.init_full_grad else 0 for c in cells]
    full_grad_cost = m * n
    comm = 0
    wire = [0] * B
    slot = meta0.slot_start
    t = 0

    def phi_for(rounds: int):
        nonlocal slot, comm
        by_aux: dict = {}
        per_cell = []
        for c in cells:
            phi = by_aux.get(id(c.aux))
            if phi is None:
                phi = by_aux[id(c.aux)] = c.backend.phi_for(c.aux, slot,
                                                            rounds)
            per_cell.append(phi)
        for i, c in enumerate(cells):
            wire[i] += (c.backend.bytes_per_step(c.aux, per_cell[i],
                                                 param_count)
                        * c.meta.gossip_payloads)
        slot += rounds
        comm += rounds
        if phi_batched:
            return transport.batch_phis(per_cell)
        return per_cell[0]

    def plan_record():
        ops.append(("record",))
        if meta0.epoch_metric == "grad":
            ep = [g / float(m * n) for g in grad_evals]
        else:
            ep = [float(t)] * B
        cols["epochs"].append(ep if batched else ep[0])
        cols["comm_rounds"].append(comm if meta0.comm_metric == "gossip"
                                   else t)
        cols["steps"].append(t)
        wire_col.append(list(wire) if batched else wire[0])

    def _no_flip():
        return np.zeros(B, np.bool_) if opost_batched else False

    def finish_chunk(idxs, phis, alphas, flags, chunk):
        """Bucket-pad and stack one chunk's xs on host (batch gather is ONE
        vectorized take per leaf — same indices as per-step sampling).
        Transition flags pad with False/0 so padded steps never fire an
        outer transition."""
        bucket = _bucket_length(chunk, record_every)
        pad = bucket - chunk
        if pad:
            if idxs:
                idxs.extend(idxs[-1:] * pad)
            phis.extend(phis[-1:] * pad)
            alphas.extend(alphas[-1:] * pad)
        keep = np.array([True] * chunk + [False] * pad, np.bool_)
        phis_st = jax.tree.map(lambda *l: _stack_wire(l), *phis)
        alphas_st = np.asarray(alphas, np.float32)   # (T,) or (T, B)
        if host_sampling:
            idx = np.stack(idxs)      # (bucket, m, bsz) or (bucket, B, m, bsz)
            if batched:
                batch = jax.tree.map(
                    lambda a: np.take_along_axis(
                        a[None, None],
                        idx.reshape(bucket, B, m, bsz,
                                    *([1] * (a.ndim - 2))),
                        axis=3), host_data)
            else:
                batch = jax.tree.map(
                    lambda a: np.take_along_axis(
                        a[None],
                        idx.reshape(bucket, m, bsz, *([1] * (a.ndim - 2))),
                        axis=2), host_data)
            xs = (batch, phis_st, alphas_st, keep)
        else:
            xs = (phis_st, alphas_st, keep)
        if transitions:
            fpad = [False] * pad
            o_post = flags["o_post"] + [_no_flip()] * pad
            xs = xs + (np.array(flags["o_pre"] + fpad, np.bool_),
                       np.asarray(o_post, np.bool_),
                       np.array(flags["e_post"] + fpad, np.bool_),
                       np.array(flags["e_k"] + [0.0] * pad, np.float32))
        ops.append(("chunk", len(chunks)))
        chunks.append(_Chunk(xs))

    def draw_idx():
        per_cell = [c.rng.integers(0, n, size=(m, bsz)) for c in cells]
        return np.stack(per_cell) if batched else per_cell[0]

    def draw_alpha(step_t: int):
        per_cell = [c.meta.stepsize(step_t) for c in cells]
        return (np.asarray(per_cell, np.float32) if batched
                else per_cell[0])

    plan_record()

    if meta0.outer_lengths is not None:
        # ---- outer/inner structure (DPSVRG, GT-SVRG) ----------------------
        just_recorded = False
        pending_outer = False
        for K in meta0.outer_lengths:
            if transitions:
                pending_outer = True
            else:
                ops.append(("outer",))
            if meta0.outer_full_grad:
                for i in range(B):
                    grad_evals[i] += full_grad_cost
            k = 0
            while k < K:
                key0 = k if meta0.record_key == "round" else t
                until = (record_every - key0 % record_every
                         if record_every else K - k)
                chunk = min(K - k, until)
                idxs, phis, alphas = [], [], []
                flags = {"o_pre": [], "o_post": [], "e_post": [], "e_k": []}
                for j in range(chunk):
                    if host_sampling:
                        idxs.append(draw_idx())
                    phis.append(phi_for(meta0.gossip_rounds(k + j + 1)))
                    alphas.append(draw_alpha(t + j + 1))
                    if transitions:
                        flags["o_pre"].append(pending_outer)
                        pending_outer = False
                        flags["o_post"].append(_no_flip())
                        flags["e_post"].append(k + j + 1 == K)
                        flags["e_k"].append(float(K))
                finish_chunk(idxs, phis, alphas, flags, chunk)
                k += chunk
                t += chunk
                for i in range(B):
                    grad_evals[i] += chunk * meta0.step_grad_factor * m * bsz
                key = k if meta0.record_key == "round" else t
                if record_every and key % record_every == 0:
                    plan_record()
                    just_recorded = True
                else:
                    just_recorded = False
            if not transitions:
                ops.append(("end_outer", K))
            if not record_every:
                plan_record()
        if record_every and meta0.final_record and not just_recorded:
            plan_record()
    else:
        # ---- flat loop (DSPG, DPG, loopless DPSVRG) -----------------------
        if record_every < 1:
            raise ValueError(
                f"{meta0.name}: flat loops need record_every >= 1")
        num_steps = meta0.num_steps
        while t < num_steps:
            until = record_every - t % record_every
            chunk_max = min(num_steps - t, until)
            idxs, phis, alphas = [], [], []
            flags = {"o_pre": [], "o_post": [], "e_post": [], "e_k": []}
            refresh = False
            chunk = 0
            for j in range(chunk_max):
                if host_sampling:
                    idxs.append(draw_idx())
                phis.append(phi_for(meta0.gossip_rounds(t + j + 1)))
                alphas.append(draw_alpha(t + j + 1))
                chunk += 1
                if transitions:
                    flags["o_pre"].append(False)
                    flags["e_post"].append(False)
                    flags["e_k"].append(0.0)
                    if has_snapshot:
                        # coin-flip snapshots fold into the chunk: one flag
                        # per (step, cell), no chunk cut — same per-cell rng
                        # draw order as the host loop (indices, then coin)
                        flips = np.array(
                            [c.rng.random() < c.meta.snapshot_prob
                             for c in cells], np.bool_)
                        if meta0.outer_full_grad:
                            for i in range(B):
                                if flips[i]:
                                    grad_evals[i] += full_grad_cost
                        flags["o_post"].append(
                            flips if opost_batched else bool(flips[0]))
                    else:
                        flags["o_post"].append(_no_flip())
                elif (has_snapshot
                        and cells[0].rng.random()
                        < meta0.snapshot_prob):
                    refresh = True   # snapshot lands here: cut the chunk
                    break
            finish_chunk(idxs, phis, alphas, flags, chunk)
            t += chunk
            for i in range(B):
                grad_evals[i] += chunk * meta0.step_grad_factor * m * bsz
            if refresh:
                ops.append(("outer",))
                if meta0.outer_full_grad:
                    grad_evals[0] += full_grad_cost
            if t % record_every == 0 or t == num_steps:
                plan_record()

    num_records = sum(1 for op in ops if op[0] == "record")
    if batched:
        cols_np = {
            "epochs": np.array(cols["epochs"], np.float64),
            "comm_rounds": np.broadcast_to(
                np.asarray(cols["comm_rounds"])[:, None],
                (num_records, B)).copy(),
            "steps": np.broadcast_to(
                np.asarray(cols["steps"])[:, None], (num_records, B)).copy(),
        }
        wire_np = np.array(wire_col, dtype=np.int64)          # (R, B)
    else:
        cols_np = {k: np.array(v) for k, v in cols.items()}
        wire_np = np.array(wire_col, dtype=np.int64)
    return _Plan(ops=ops, chunks=chunks, cols=cols_np, wire=wire_np,
                 num_records=num_records, phi_batched=phi_batched,
                 opost_batched=opost_batched)


def _nbytes(tree) -> int:
    """Bytes of a pytree's leaves; a device array reports its own without
    a copy."""
    return sum(leaf.nbytes if hasattr(leaf, "nbytes")
               else np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))


def _staged_bytes(chunks) -> int:
    return _nbytes([c.xs for c in chunks])


def _leaves_on(tree, device: bool) -> list:
    """The leaves of ``tree`` that are (``device``) or are not device
    arrays."""
    return [leaf for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array) == device]


def _new_ledger() -> dict:
    """The driver-initiated transfer ledger: events and their bytes, each
    way."""
    return {"h2d": 0, "d2h": 0, "bytes_h2d": 0, "bytes_d2h": 0}


def _moved(transfers: dict, way: str, tree) -> int:
    """Enter one transfer of ``tree`` (``way``: "h2d" or "d2h") in the
    ledger; -> its bytes."""
    nbytes = _nbytes(tree)
    transfers[way] += 1
    transfers["bytes_" + way] += nbytes
    return nbytes


def _ledger(transfers: dict) -> dict:
    """The ledger as ``RunResult.extras`` columns."""
    return {"transfers_h2d": transfers["h2d"],
            "transfers_d2h": transfers["d2h"],
            "bytes_h2d": transfers["bytes_h2d"],
            "bytes_d2h": transfers["bytes_d2h"]}


def _warn_staging(staged: int, cells: int = 1) -> None:
    """Warn when the one-shot staging transfer gets large.  ``cells``
    reflects the sweep batch axis: a batched sweep stages ALL cells' inputs
    at once, so the threshold applies to the TOTAL, not per cell."""
    if staged > 1 << 30:
        where = (f"for all {cells} sweep cells " if cells > 1 else "")
        warnings.warn(
            f"resident staging ships {staged / 2**30:.1f} GiB of "
            f"pre-sampled inputs {where}to the device at once; for long "
            f"runs use sampling='device' (in-scan minibatch gathers, zero "
            f"batch staging) or the scan path", RuntimeWarning,
            stacklevel=4)


def _node_shard_mesh(mesh, aux, m: int):
    """Resolve the mesh + axis name ``shard="nodes"`` partitions the stacked
    ``(m, d)`` node axis over.  Preference order: the caller's ``mesh`` ->
    the mesh the resolved transport already built (the ``ppermute``
    backend's aux carries one; ``compressed`` wraps it) -> a fresh 1-D mesh
    over every visible device.  The chosen axis size must divide ``m``
    (each device owns a contiguous block of simulated nodes)."""
    if mesh is None:
        mesh = getattr(aux, "mesh", None)
    if mesh is None:
        # compressed transports carry the inner transport's aux
        inner = getattr(aux, "inner_aux", None)
        mesh = getattr(inner, "mesh", None)
    if mesh is None:
        ndev = len(jax.devices())
        if m % ndev != 0:
            raise ValueError(
                f"shard='nodes' partitions the stacked (m, d) state across "
                f"the {ndev} visible device(s), but m={m} is not divisible "
                f"by the device count; pass mesh= with an axis whose size "
                f"divides m")
        return mesh_lib.make_mesh((ndev,), ("nodes",)), "nodes"
    for axis, size in mesh.shape.items():
        if size and m % size == 0:
            return mesh, axis
    raise ValueError(f"shard='nodes': mesh {dict(mesh.shape)} has no axis "
                     f"whose size divides m={m}")


def _run_resident(algo, problem, backend, aux, rng, *, m: int,
                  n: int, param_count: int, record_every: int, sampling: str,
                  extra_metrics, transfers,
                  device_transitions="auto", kernel: str = "xla",
                  mesh=None, shard=None) -> RunResult:
    meta = algo.meta
    if extra_metrics:
        raise ValueError(
            "resident=True records metrics on device; host-side "
            "extra_metrics callables need the host or scan path")
    has_batch = meta.batch_size > 0
    device_sampling = has_batch and sampling == "device"
    transitions = _resolve_transitions(algo, device_transitions)

    run_id = spans.next_run()

    # one host copy of the dataset for index gathering (the scan path pays
    # the same once-per-run pull); device sampling skips it entirely
    if has_batch and sampling == "host":
        pulled = _leaves_on(problem.full_data, True)
        d2h = _moved(transfers, "d2h", pulled) if pulled else 0
        with spans.span(spans.STAGE, run=run_id, h2d_bytes=0, d2h_bytes=d2h):
            host_data = jax.tree.map(np.asarray, problem.full_data)
    else:
        host_data = None
    # the device PRNG seed is drawn from the run's rng stream, so
    # resident+device runs are reproducible from the same `seed`
    key_seed = int(rng.integers(0, 2**31 - 1)) if device_sampling else 0

    with spans.span(spans.PLAN, run=run_id) as plan_span:
        plan = _plan_resident(
            [_PlanCell(meta, rng, backend, aux)], m=m, n=n,
            param_count=param_count, record_every=record_every,
            sampling=sampling, host_data=host_data, transitions=transitions)
        plan_span.set_metadata(steps=int(plan.cols["steps"][-1]),
                               chunks=len(plan.chunks))

    exec_chunk = _make_resident_exec(algo, sampling, transitions, kernel)
    record_kernel = _make_record_kernel(problem, meta)

    # shard="nodes": every placement below becomes an explicit NamedSharding
    # on the resolved mesh — the (m, ...) leaves split on the node axis,
    # everything else replicated — and the SAME compiled executors then run
    # SPMD under GSPMD (donated carries keep their sharding)
    if shard == "nodes":
        smesh, saxis = _node_shard_mesh(mesh, aux, m)
        NS, P = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
        rep = NS(smesh, P())
        node0 = NS(smesh, P(saxis))

        def _node_leaf(l):
            return node0 if (getattr(l, "ndim", 0) >= 1
                             and l.shape[0] == m) else rep

        def _xs_shardings(xs):
            # components follow _plan_resident's xs layout: a host-sampled
            # batch tree leads with leaves (bucket, m, bsz, ...) — node axis
            # at 1; phis / alphas / keep / transition flags are tiny and
            # stay replicated
            out = []
            for i, comp in enumerate(xs):
                if has_batch and sampling == "host" and i == 0:
                    out.append(jax.tree.map(
                        lambda l: NS(smesh, P(None, saxis)), comp))
                else:
                    out.append(jax.tree.map(lambda l: rep, comp))
            return tuple(out)

    def pack(state):
        if device_sampling:
            key = jax.random.PRNGKey(key_seed)
            if shard == "nodes":
                key = jax.device_put(key, rep)
            return (state, key)
        return state

    def unpack(carry):
        return carry[0] if device_sampling else carry

    def repack(carry, state):
        return (state, carry[1]) if device_sampling else state

    # dataset staging only transfers when the problem holds host arrays
    # (jnp.asarray on a committed device array is a no-op).  ONE staging
    # transfer ships every chunk's xs (and nothing per-step thereafter); the
    # shielded state copy protects caller-owned buffers (problem.x0) from
    # the donated carries.  NOTE the memory trade: host-sampled batches for
    # the WHOLE run live on device at once — O(num_steps * m * batch *
    # feature) bytes; warn when that gets big (sampling="device" stages no
    # batches at all)
    pushed = _leaves_on(problem.full_data, False)
    h2d = _moved(transfers, "h2d", pushed) if pushed else 0
    _warn_staging(_staged_bytes(plan.chunks))
    h2d += _moved(transfers, "h2d", [c.xs for c in plan.chunks])
    with spans.span(spans.STAGE, run=run_id, h2d_bytes=h2d, d2h_bytes=0):
        if shard == "nodes":
            data_dev = jax.device_put(problem.full_data,
                                      jax.tree.map(_node_leaf,
                                                   problem.full_data))
            staged = jax.device_put(
                [c.xs for c in plan.chunks],
                [_xs_shardings(c.xs) for c in plan.chunks])
        else:
            data_dev = jax.tree.map(jnp.asarray, problem.full_data)
            staged = jax.device_put([c.xs for c in plan.chunks])

        state = algo.init()
        state = inject_mix_state(algo, backend, aux, state)
        if transitions and algo.device_state is not None:
            state = algo.device_state(state)
        state = _shield_for_donation(state)
        if shard == "nodes":
            # splits the (m, ...) state leaves — including any error-feedback
            # mix state, which thereby stays shard-local — over the node axis
            state = jax.device_put(state, jax.tree.map(_node_leaf, state))

        carry = pack(state)
        bufs = (jnp.zeros(plan.num_records, jnp.float32),
                jnp.zeros(plan.num_records, jnp.float32),
                jnp.zeros((), jnp.int32))
        if shard == "nodes":
            # the record kernel mixes bufs with sharded params — colocate
            # them on the mesh (replicated) so the jit sees one device set
            bufs = jax.device_put(bufs, rep)

    guard = _RESIDENT_DISPATCH_GUARD
    with spans.span(spans.DISPATCH, run=run_id, dispatches=len(plan.ops)):
        for op in plan.ops:
            kind = op[0]
            if kind == "chunk":
                with guard():
                    carry = exec_chunk(carry, staged[op[1]], data_dev)
            elif kind == "record":
                with guard():
                    bufs = record_kernel(bufs,
                                         algo.get_params(unpack(carry)),
                                         data_dev)
            elif kind == "outer":
                carry = repack(carry, _unalias_for_donation(
                    algo.outer(unpack(carry))))
            else:  # ("end_outer", K)
                state = unpack(carry)
                if algo.end_outer is not None:
                    state = algo.end_outer(state, op[1])
                carry = repack(carry, _unalias_for_donation(state))

    d2h = _moved(transfers, "d2h", bufs)
    with spans.span(spans.PULL, run=run_id, d2h_bytes=d2h):
        objective, consensus, _ = jax.device_get(bufs)   # the ONE history pull

    history = RunHistory(
        objective=np.asarray(objective, np.float64),
        consensus=np.asarray(consensus, np.float64),
        epochs=plan.cols["epochs"],
        comm_rounds=plan.cols["comm_rounds"],
        steps=plan.cols["steps"])
    extras = {"wire_bytes": plan.wire, **_ledger(transfers)}
    return RunResult(params=algo.get_params(unpack(carry)), history=history,
                     extras=extras)


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------

def inject_mix_state(algo, backend, aux, state):
    """Give ``state`` the transport state a stateful backend needs.

    The algorithm owns WHERE the state lives (its ``cstate`` slot(s), via
    ``Algorithm.init_mix_state``); the backend owns WHAT the state is.
    Factories whose ``init_mix_state`` takes a ``make`` initializer get the
    resolved backend's own ``init_mix_state(aux, x0)`` bound to its aux
    (scenario delay buffers, ...); legacy single-argument initializers keep
    their built-in error-feedback default (tests call them directly)."""
    if not backend.needs_mix_state:
        return state
    if algo.init_mix_state is None:
        raise ValueError(
            f"{algo.meta.name} does not thread a gossip mix state "
            f"(Algorithm.init_mix_state is None), so it cannot be "
            f"driven by the stateful {backend.name!r} transport")
    if len(inspect.signature(algo.init_mix_state).parameters) >= 2:
        return algo.init_mix_state(
            state, make=functools.partial(backend.init_mix_state, aux))
    return algo.init_mix_state(state)


def _resolved_backend(gossip, schedule, meta, mesh):
    """Resolve the transport and honor hp-level quantization: a method that
    quantizes its own gossip payload (``AlgoMeta.compress_bits``) gets its
    resolved transport wrapped in a ``CompressedBackend`` at those bits, so
    the wire accounting matches what actually moves (conflicting explicit
    compressed transports raise)."""
    backend = transport.resolve_backend(gossip, schedule, meta, mesh)
    if meta.compress_bits is not None:
        if getattr(backend, "scenario_transport", False):
            raise ValueError(
                f"the algorithm quantizes its own gossip "
                f"(meta.compress_bits={meta.compress_bits}) but the "
                f"requested scenario transport owns the full wire stack — "
                f"pass the quantization inside the scenario spec "
                f"(compress_bits=...) instead")
        if isinstance(backend, transport.CompressedBackend):
            if backend.bits != meta.compress_bits:
                raise ValueError(
                    f"conflicting compression: the algorithm quantizes its "
                    f"gossip at {meta.compress_bits} bits "
                    f"(meta.compress_bits) but the requested transport "
                    f"compresses at {backend.bits} bits — drop one of the "
                    f"two, or make them agree")
        else:
            backend = transport.CompressedBackend(inner=backend,
                                                  bits=meta.compress_bits)
    return backend


def run(algo: algorithm_lib.Algorithm,
        problem: algorithm_lib.Problem,
        schedule: graphs.MixingSchedule,
        exec: "ExecSpec | None" = None,
        *,
        seed: int = 0,
        record_every: int = 1,
        extra_metrics: dict | None = None,
        scan=UNSET,
        resident=UNSET,
        sampling=UNSET,
        device_transitions=UNSET,
        kernel=UNSET,
        gossip=UNSET,
        mesh=UNSET,
        gossip_mode: str | None = None) -> RunResult:
    """Drive ``algo`` on ``problem`` over the time-varying ``schedule``.

    exec:         an :class:`~repro.core.exec_spec.ExecSpec` — the ONE
                  execution specification (path, sampling, transitions,
                  kernel, transport, mesh, shard).  ``None`` (default) is
                  the host loop.  Field semantics:

                  * ``scan``: the ``lax.scan`` chunked fast path.
                  * ``resident``: keep the entire run device-resident —
                    plan on host, stage in one transfer, execute donated
                    compiled chunks, record metrics on device, pull the
                    history once at run end.
                  * ``sampling``: "host" (default) draws minibatch indices
                    from the same ``np.random`` stream as the host/scan
                    paths (histories agree to float tolerance); "device"
                    (resident only) threads a ``jax.random`` key through
                    the scan carry and gathers minibatches inside the
                    compiled chunk — a different sample stream, zero batch
                    staging.
                  * ``device_transitions`` (resident only): "auto" folds
                    ``outer``/``end_outer`` into the compiled chunks
                    whenever the algorithm declares the traceable contract
                    (all registered algorithms do); ``False`` keeps host
                    dispatches; ``True`` requires the contract.
                  * ``kernel`` (resident only): "xla" plain step;
                    "pallas" fused resident-step body where a fused
                    lowering exists; "auto" additionally keeps XLA at
                    small d.  Histories agree across kernels.
                  * ``gossip``: transport backend — a
                    ``transport.GOSSIP_BACKENDS`` name, an instance, or
                    "auto" (select by schedule bandwidth and mesh).
                  * ``mesh``: device mesh — enables the ``ppermute``
                    transport (node axis of size m) and carries the
                    sharding mesh for ``shard``.
                  * ``shard``: ``"nodes"`` (resident only) partitions the
                    stacked ``(m, d)`` node axis over the mesh via GSPMD —
                    staged inputs/dataset/state placed shard-wise, the
                    same donated chunk executors run SPMD, histories equal
                    to the unsharded run to float tolerance with the O(1)
                    transfer ledger intact.  ``"cells"`` is the sweep-axis
                    counterpart and only valid on ``run_sweep``.
    record_every: history cadence in inner steps; 0 = once per outer round
                  (outer/inner methods only).
    extra_metrics: ``{name: fn(stacked_params) -> float}`` recorded alongside
                  the standard history columns (returned in ``extras``, next
                  to the always-present ``wire_bytes`` column).  Host-side
                  callables — unavailable under ``resident=True``.
    scan, resident, sampling, device_transitions, kernel, gossip, mesh:
                  DEPRECATED keyword spellings of the ExecSpec fields
                  (one-release shim; combining them with ``exec=`` raises).
    gossip_mode:  DEPRECATED alias for the spec's ``gossip`` field.
    """
    meta = algo.meta
    if gossip_mode is not None:
        warnings.warn(
            "runner.run(gossip_mode=...) is deprecated; use "
            "exec=ExecSpec(gossip=...) (same names, plus 'ppermute', "
            "'compressed', and 'auto')",
            DeprecationWarning, stacklevel=2)
        gossip = gossip_mode
        # one warning per call: the mapped kwarg would trip resolve_exec's
        # own shim warning on top of the gossip_mode one above
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            spec = exec_spec_lib.resolve_exec(
                exec, "runner.run", scan=scan, resident=resident,
                sampling=sampling, device_transitions=device_transitions,
                kernel=kernel, gossip=gossip, mesh=mesh)
    else:
        spec = exec_spec_lib.resolve_exec(
            exec, "runner.run", scan=scan, resident=resident,
            sampling=sampling, device_transitions=device_transitions,
            kernel=kernel, gossip=gossip, mesh=mesh)
    if spec.shard == "cells":
        raise ValueError("shard='cells' partitions a batched sweep's CELL "
                         "axis — use runner.run_sweep; a single run shards "
                         "its node axis with shard='nodes'")
    scan, resident, sampling = spec.scan, spec.resident, spec.sampling
    device_transitions, kernel = spec.device_transitions, spec.kernel
    gossip, mesh, shard = spec.gossip, spec.mesh, spec.shard
    backend = _resolved_backend(gossip, schedule, meta, mesh)
    aux = backend.prepare(schedule, meta, mesh=mesh)
    rng = np.random.default_rng(seed)
    m = jax.tree.leaves(problem.x0)[0].shape[0]
    n = jax.tree.leaves(problem.full_data)[0].shape[1]
    param_count = transport.node_param_count(problem.x0)
    # driver-initiated host<->device transfer EVENTS (coarse: one per staged
    # tree / per metric pull, not per buffer) and the bytes they move — the
    # resident path's O(1) claim is asserted against these in benchmarks
    # and tests
    transfers = _new_ledger()

    if resident:
        return _run_resident(algo, problem, backend, aux, rng,
                             m=m, n=n, param_count=param_count,
                             record_every=record_every, sampling=sampling,
                             extra_metrics=extra_metrics,
                             transfers=transfers,
                             device_transitions=device_transitions,
                             kernel=kernel, mesh=mesh, shard=shard)

    obj = problem.objective_fn or (
        lambda p: objective_value(problem.loss_fn, problem.prox, p,
                                  problem.full_data))
    rec = Recorder(obj, meta, m, n, extra_metrics)
    exec_chunk = _make_scan_exec(algo) if scan else None
    # sample minibatches from a host-side copy: per-step np gathers on device
    # arrays would silently round-trip the whole dataset every step
    if meta.batch_size > 0:
        pulled = _leaves_on(problem.full_data, True)
        if pulled:
            _moved(transfers, "d2h", pulled)
        host_data = jax.tree.map(np.asarray, problem.full_data)
    else:
        host_data = problem.full_data

    state = algo.init()
    state = inject_mix_state(algo, backend, aux, state)
    grad_evals = m * n if meta.init_full_grad else 0
    full_grad_cost = m * n
    comm = 0
    wire = 0
    slot = meta.slot_start
    t = 0

    def phi_for(rounds: int):
        nonlocal slot, comm, wire
        phi = backend.phi_for(aux, slot, rounds)
        slot += rounds
        comm += rounds
        # gossip_payloads: gradient tracking gossips the iterate AND the
        # tracker with the same phi, so its wire cost is 2x per round
        wire += (backend.bytes_per_step(aux, phi, param_count)
                 * meta.gossip_payloads)
        return phi

    def device_phi(phi):
        phi = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), phi)
        _moved(transfers, "h2d", phi)
        return phi

    def pad_chunk(batches, phis, alphas, chunk):
        """Pad collected inputs to the bucket length with masked-out repeats
        of the last real entry (no extra rng draws, no extra gossip slots)."""
        bucket = _bucket_length(chunk, record_every)
        pad = bucket - chunk
        if pad:
            if batches:
                batches.extend(batches[-1:] * pad)
            phis.extend(phis[-1:] * pad)
            alphas.extend(alphas[-1:] * pad)
        return [True] * chunk + [False] * pad

    def do_record():
        params = algo.get_params(state)
        _moved(transfers, "d2h", np.float32(0))      # the objective
        if meta.track_consensus:
            _moved(transfers, "d2h", params)         # every node's iterate
        rec.record(params, t=t, grad_evals=grad_evals, comm_rounds=comm,
                   wire_bytes=wire)

    def run_chunk(state, batches, phis, alphas, keep):
        xs = _stack_inputs(meta, batches, phis, alphas, keep)
        _moved(transfers, "h2d", xs)
        return exec_chunk(state, xs)

    do_record()

    if meta.outer_lengths is not None:
        # ---- outer/inner structure (DPSVRG, GT-SVRG) ----------------------
        just_recorded = False
        for K in meta.outer_lengths:
            state = algo.outer(state)
            if meta.outer_full_grad:
                grad_evals += full_grad_cost
            k = 0
            while k < K:
                if scan:
                    key0 = k if meta.record_key == "round" else t
                    until = (record_every - key0 % record_every
                             if record_every else K - k)
                    chunk = min(K - k, until)
                    batches, phis, alphas = [], [], []
                    for j in range(chunk):
                        if meta.batch_size > 0:
                            batches.append(sample_batch(
                                rng, host_data, meta.batch_size))
                        phis.append(phi_for(meta.gossip_rounds(k + j + 1)))
                        alphas.append(meta.stepsize(t + j + 1))
                    keep = pad_chunk(batches, phis, alphas, chunk)
                    state = run_chunk(state, batches, phis, alphas, keep)
                    k += chunk
                    t += chunk
                    grad_evals += (chunk * meta.step_grad_factor * m
                                   * meta.batch_size)
                else:
                    k += 1
                    t += 1
                    batch = (sample_batch(rng, host_data, meta.batch_size)
                             if meta.batch_size > 0 else None)
                    if meta.batch_size > 0:
                        _moved(transfers, "h2d", batch)
                    phi = device_phi(phi_for(meta.gossip_rounds(k)))
                    state = algo.step(state, batch, phi,
                                      jnp.float32(meta.stepsize(t)))
                    grad_evals += meta.step_grad_factor * m * meta.batch_size
                key = k if meta.record_key == "round" else t
                if record_every and key % record_every == 0:
                    do_record()
                    just_recorded = True
                else:
                    just_recorded = False
            if algo.end_outer is not None:
                state = algo.end_outer(state, K)
            if not record_every:
                do_record()
        if record_every and meta.final_record and not just_recorded:
            do_record()
    else:
        # ---- flat loop (DSPG, DPG, loopless DPSVRG) -----------------------
        if record_every < 1:
            raise ValueError(
                f"{meta.name}: flat loops need record_every >= 1")
        num_steps = meta.num_steps
        while t < num_steps:
            if scan:
                until = record_every - t % record_every
                chunk_max = min(num_steps - t, until)
                batches, phis, alphas = [], [], []
                refresh = False
                chunk = 0
                for j in range(chunk_max):
                    if meta.batch_size > 0:
                        batches.append(sample_batch(
                            rng, host_data, meta.batch_size))
                    phis.append(phi_for(meta.gossip_rounds(t + j + 1)))
                    alphas.append(meta.stepsize(t + j + 1))
                    chunk += 1
                    if (meta.snapshot_prob is not None
                            and rng.random() < meta.snapshot_prob):
                        refresh = True   # snapshot lands here: cut the chunk
                        break
                keep = pad_chunk(batches, phis, alphas, chunk)
                state = run_chunk(state, batches, phis, alphas, keep)
                t += chunk
                grad_evals += chunk * meta.step_grad_factor * m * meta.batch_size
                if refresh:
                    state = algo.outer(state)
                    if meta.outer_full_grad:
                        grad_evals += full_grad_cost
            else:
                t += 1
                batch = (sample_batch(rng, host_data, meta.batch_size)
                         if meta.batch_size > 0 else None)
                if meta.batch_size > 0:
                    _moved(transfers, "h2d", batch)
                phi = device_phi(phi_for(meta.gossip_rounds(t)))
                state = algo.step(state, batch, phi,
                                  jnp.float32(meta.stepsize(t)))
                grad_evals += meta.step_grad_factor * m * meta.batch_size
                if (meta.snapshot_prob is not None
                        and rng.random() < meta.snapshot_prob):
                    state = algo.outer(state)
                    if meta.outer_full_grad:
                        grad_evals += full_grad_cost
            if t % record_every == 0 or t == num_steps:
                do_record()

    extras = {**rec.extras(), **_ledger(transfers)}
    return RunResult(params=algo.get_params(state), history=rec.history(),
                     extras=extras)


# Batched hyperparameter sweeps (one staged device program per fig sweep)
# live in core.sweep; re-exported here so `runner.run_sweep` is the public
# entry next to `runner.run`.  The import sits at module bottom because
# sweep builds on the planner/executor machinery above.
from .sweep import SweepResult, run_sweep  # noqa: E402
