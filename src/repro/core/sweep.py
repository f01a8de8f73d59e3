"""Batched resident sweeps: a whole hyperparameter grid as ONE device
program.

The paper's experiments are all *sweeps* — λ grids (Fig. 4), connectivity
grids (Fig. 5), multi-seed convergence curves (Fig. 1) — and running each
cell through ``runner.run(resident=True)`` still pays one staging transfer
and one dispatch loop PER CELL.  :func:`run_sweep` removes that seam: the
grid expands into a batch axis, the per-cell control flow (identical by
construction — the driver validates it) is planned ONCE, every cell's
inputs are staged in a single ``jax.device_put``, the donated chunk
executors are ``jax.vmap``-ped over the cell axis, outer-round transitions
run inside the compiled chunks (``lax.cond`` on the precomputed round
schedule, via the ``Algorithm.outer_traced`` contract — zero per-round host
dispatches), and ONE stacked history comes back at the end.  An entire fig
sweep is one device program with O(1) host<->device transfers total — and
every cell runs under the exact schedule every other cell sees, which is
what makes GT-SVRG-style cross-method comparisons meaningful.

The contract
------------

``run_sweep(build, grid, schedule)`` takes a CELL FACTORY

    build(**cell) -> (Algorithm, Problem)

and a ``grid`` mapping axis names to value lists.  Two axis names are
reserved and handled by the driver rather than passed to ``build``:

* ``"seed"`` — per-cell ``np.random`` stream (minibatch indices, loopless
  coin flips, device-sampling key), drawn in the same order as a sequential
  ``runner.run(seed=...)`` so batched histories match sequential ones to
  float tolerance;
* ``"schedule"`` — per-cell :class:`~repro.core.graphs.MixingSchedule`
  (topology grids).  Cells may gossip over different schedules as long as
  their wire representations share static structure — ``gossip="dense"``
  always does; banded cells need a common offset union
  (:func:`~repro.core.transport.batch_phis` raises otherwise).

Everything else (λ, step sizes, init points, ...) must be NUMERIC and reach
``build`` twice: once concretely per cell (host-side validation + planning
— step-size schedules, loop lengths), and once as jax tracers inside the
batched program (vmapped over the cell axis), where the factory's closures
(e.g. ``prox.l1(lam)``) trace through.  Axes that change the run STRUCTURE
(loop lengths, batch sizes, gossip-round policies, datasets) are rejected
with a "ragged sweep grid" error — batch what shares a trace shape, loop
over the rest.

Execution is selected by an :class:`~repro.core.exec_spec.ExecSpec` (the
same spec ``runner.run`` consumes): the default ``ExecSpec(resident=True)``
builds the batched program; ``batched=False`` runs the cells as sequential
resident runs (the baseline the batched path is benchmarked against);
``ExecSpec(resident=False)`` drives the host/scan paths sequentially; and
``ExecSpec(shard="cells")`` partitions the batched program's cell axis over
a device mesh via GSPMD (each device executes a contiguous grid slice).
All modes return the same :class:`SweepResult` with (records, cells)
history columns, so equivalence is one ``np.testing.assert_allclose`` away.
"""

from __future__ import annotations

import collections
import functools
import itertools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import (algorithm as algorithm_lib, exec_spec as exec_spec_lib,
               mesh as mesh_lib, spans, transport)
from .exec_spec import UNSET, ExecSpec

__all__ = ["SweepResult", "expand_grid", "run_sweep"]

# Compiled sweep executors are cached on the IDENTITY of the user's cell
# factory: the executor re-traces `build` per cell, so any weaker key could
# serve a program compiled from a different closure (stale dataset
# constants).  The flip side is retention — each key pins whatever the
# factory closes over (typically the dataset) — so sweep executors get
# their own SMALL LRU instead of the runner's 64-entry cache, and a
# factory defined inline per call simply recompiles (reuse one callable
# across run_sweep calls to stay warm).  Cleared by
# ``runner.reset_executable_caches()``.
_SWEEP_EXEC_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()
_SWEEP_EXEC_CACHE_MAX = 8


def _shared_sweep_exec(key: tuple, make: Callable[[], Callable]) -> Callable:
    return algorithm_lib.memoize_into(_SWEEP_EXEC_CACHE,
                                      _SWEEP_EXEC_CACHE_MAX, key, make)

_RESERVED_AXES = ("seed", "schedule")

# AlgoMeta fields that define the run's STRUCTURE: every cell of a batched
# sweep must agree on them (numeric fields like stepsize values and
# snapshot probabilities are free to vary).
_STRUCT_FIELDS = (
    "outer_lengths", "num_steps", "batch_size", "step_grad_factor",
    "outer_full_grad", "init_full_grad", "gossip_payloads", "slot_start",
    "track_consensus", "comm_metric", "epoch_metric", "record_key",
    "final_record", "compress_bits",
)


class SweepResult(NamedTuple):
    """Stacked result of a sweep: every history column is
    ``(records, cells)``; ``params`` leaves carry a leading cell axis;
    ``grid`` is the expanded cell list (reserved axes included).
    ``extras['wire_bytes']`` is ``(records, cells)``;
    ``extras['transfers_h2d'/'transfers_d2h']`` count driver-initiated
    transfer events for the WHOLE sweep (O(1) on the batched path), and
    ``extras['bytes_h2d'/'bytes_d2h']`` the bytes they moved."""

    grid: list
    params: Any
    history: Any                   # runner.RunHistory, columns (R, B)
    extras: dict

    def cell(self, i: int):
        """The i-th cell's result as a plain ``runner.RunResult``."""
        from . import runner as runner_lib
        hist = runner_lib.RunHistory(
            **{f: np.asarray(getattr(self.history, f))[:, i]
               for f in runner_lib.RunHistory._fields})
        extras = dict(self.extras)
        extras["wire_bytes"] = np.asarray(self.extras["wire_bytes"])[:, i]
        return runner_lib.RunResult(
            params=jax.tree.map(lambda l: l[i], self.params),
            history=hist, extras=extras)


def expand_grid(grid: dict, mode: str = "product") -> list:
    """Expand ``{axis: values}`` into a list of cell dicts — the cartesian
    ``"product"`` (default) or the elementwise ``"zip"`` of the axes."""
    if not grid:
        raise ValueError("empty sweep grid: pass at least one axis, e.g. "
                         "{'seed': [0, 1, 2]} or {'lam': [1e-3, 1e-2]}")
    names = list(grid)
    values = [list(v) for v in grid.values()]
    if any(len(v) == 0 for v in values):
        raise ValueError(f"sweep grid axis with no values: "
                         f"{[n for n, v in zip(names, values) if not v]}")
    if mode == "product":
        combos = itertools.product(*values)
    elif mode == "zip":
        lens = sorted({len(v) for v in values})
        if len(lens) > 1:
            raise ValueError(
                f"zip-mode sweep axes must share one length, got "
                f"{ {n: len(v) for n, v in zip(names, values)} }")
        combos = zip(*values)
    else:
        raise ValueError(f"unknown grid mode {mode!r}: 'product' or 'zip'")
    return [dict(zip(names, combo)) for combo in combos]


# ---------------------------------------------------------------------------
# Grid validation: reject anything that changes the trace shape
# ---------------------------------------------------------------------------

def _ragged(what: str) -> ValueError:
    return ValueError(
        f"ragged sweep grid: {what}.  A batched sweep runs every cell "
        f"through ONE compiled program, so cells must share the run "
        f"structure (loop lengths, batch sizes, gossip policy, dataset, "
        f"parameter shapes); sweep numeric hyperparameters — seeds, step "
        f"sizes, lambdas, init points — and loop over structural ones.")


def _validate_cells(cells, built, schedules):
    metas = [algo.meta for algo, _ in built]
    meta0 = metas[0]
    for i, meta in enumerate(metas[1:], 1):
        for f in _STRUCT_FIELDS:
            if getattr(meta, f) != getattr(meta0, f):
                raise _ragged(
                    f"cell {i} ({cells[i]}) has AlgoMeta.{f}="
                    f"{getattr(meta, f)!r} vs {getattr(meta0, f)!r} in "
                    f"cell 0 ({cells[0]})")
        if (meta.snapshot_prob is None) != (meta0.snapshot_prob is None):
            raise _ragged(
                f"cell {i} ({cells[i]}) toggles coin-flip snapshots "
                f"(snapshot_prob {meta.snapshot_prob!r} vs "
                f"{meta0.snapshot_prob!r})")
    horizon = (max(meta0.outer_lengths)
               if meta0.outer_lengths is not None
               else (meta0.num_steps or 1))
    rounds0 = [meta0.gossip_rounds(k) for k in range(1, horizon + 1)]
    for i, meta in enumerate(metas[1:], 1):
        if [meta.gossip_rounds(k)
                for k in range(1, horizon + 1)] != rounds0:
            raise _ragged(
                f"cell {i} ({cells[i]}) uses a different gossip-rounds "
                f"policy — cells share one staged gossip-product stream")

    p0 = built[0][1]
    x0_def = jax.tree.structure(p0.x0)
    x0_shapes = [(np.shape(l), np.asarray(l).dtype)
                 for l in jax.tree.leaves(p0.x0)]
    data_def = jax.tree.structure(p0.full_data)
    data_leaves0 = jax.tree.leaves(p0.full_data)
    for i, (_, p) in enumerate(built[1:], 1):
        if jax.tree.structure(p.x0) != x0_def or \
                [(np.shape(l), np.asarray(l).dtype)
                 for l in jax.tree.leaves(p.x0)] != x0_shapes:
            raise _ragged(f"cell {i} ({cells[i]}) changes the x0 pytree "
                          f"structure/shape")
        if jax.tree.structure(p.full_data) != data_def:
            raise _ragged(f"cell {i} ({cells[i]}) changes the dataset "
                          f"pytree structure")
        for a, b in zip(data_leaves0, jax.tree.leaves(p.full_data)):
            if a is b:
                continue
            if np.shape(a) != np.shape(b) or \
                    not np.array_equal(np.asarray(a), np.asarray(b)):
                raise _ragged(
                    f"cell {i} ({cells[i]}) runs on a DIFFERENT dataset — "
                    f"the sweep stages one shared dataset")

    m0 = schedules[0].m
    for i, s in enumerate(schedules[1:], 1):
        if s.m != m0:
            raise _ragged(f"cell {i} ({cells[i]}) gossips over m={s.m} "
                          f"nodes vs m={m0} in cell 0")


def _require_traced(algo):
    meta = algo.meta
    needs_outer = (meta.outer_lengths is not None
                   or meta.snapshot_prob is not None)
    if not needs_outer:
        return
    needs_end = meta.outer_lengths is not None and algo.end_outer is not None
    if (algo.outer is not None and algo.outer_traced is None) or \
            (needs_end and algo.end_outer_traced is None):
        raise ValueError(
            f"{meta.name}: batched sweeps fold outer-round transitions "
            f"into the compiled program and need the traceable contract "
            f"(Algorithm.outer_traced"
            f"{' + end_outer_traced' if needs_end else ''}); run with "
            f"batched=False to sweep this algorithm sequentially")


# ---------------------------------------------------------------------------
# In-trace cell rebuilds
# ---------------------------------------------------------------------------

def _trace_build(build: Callable, cell: dict):
    """Rebuild one cell INSIDE the batched trace: ``cell`` values arrive as
    jax tracers (vmapped over the cell axis), so the factory's closures
    (``prox.l1(lam)``, loss weights, ...) trace through and the compiled
    program computes every cell's math from its own scalars.  Steps built
    here are ephemeral — never memoized into the shared caches."""
    with algorithm_lib.ephemeral_steps():
        try:
            out = build(**cell)
        except (jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError) as e:
            raise ValueError(
                f"sweep axes {sorted(cell)} reach build() as TRACED scalars "
                f"inside the batched program; the factory must only use "
                f"them in jax-traceable numerics (loss/prox math, hyper-"
                f"parameter dataclasses), not in host control flow or loop "
                f"lengths.  Original error: {e}") from e
    return out


# ---------------------------------------------------------------------------
# Batched executors (vmapped over the cell axis, donated carries)
# ---------------------------------------------------------------------------

def _xs_axes(meta, sampling: str, plan) -> tuple:
    """vmap in_axes over one chunk's xs: per-cell leaves carry the cell
    axis at position 1 (behind scan's time axis), shared leaves are None."""
    has_batch = meta.batch_size > 0
    host_sampling = has_batch and sampling == "host"
    axes = (1 if plan.phi_batched else None,   # phis
            1,                                 # alphas (T, B)
            None,                              # keep
            None,                              # outer-before flags
            1 if plan.opost_batched else None,  # coin-flip flags
            None,                              # end-of-round flags
            None)                              # end-of-round K
    if host_sampling:
        return (1,) + axes                     # batch tree leaves (T, B, ...)
    return axes


def _make_sweep_exec(template, build, sampling: str, plan, cache_key,
                     kernel: str = "xla"):
    """One compiled dispatch executing a whole (padded) chunk for EVERY
    cell: ``jax.vmap`` over the cell axis of the donated carry, with the
    algorithm rebuilt per cell inside the trace (cell hyperparameters are
    tracers) and outer transitions applied under ``lax.cond`` from the
    per-step flags in the xs."""
    from . import runner as runner_lib

    meta = template.meta
    has_batch = meta.batch_size > 0
    device_sampling = has_batch and sampling == "device"
    has_opre = meta.outer_lengths is not None and template.outer is not None
    has_opost = (meta.snapshot_prob is not None
                 and template.outer is not None)
    has_end = (meta.outer_lengths is not None
               and template.end_outer is not None)
    xs_axes = _xs_axes(meta, sampling, plan)

    def make():
        def exec_impl(carry, xs, data, cells):
            def one_cell(carry_c, xs_c, cell):
                algo_t, _ = _trace_build(build, cell)
                # the fused resident-step kernel swaps in exactly as on
                # the single-run path (same _resolve_kernel_step
                # contract); resolved under ephemeral_steps like the rest
                # of the in-trace rebuild so the fused inner builders
                # never memoize tracer-closing closures
                with algorithm_lib.ephemeral_steps():
                    step_fn = runner_lib._resolve_kernel_step(algo_t, kernel)
                # the scan body is the runner's — one implementation for
                # the single-run and batched paths — specialized here with
                # this cell's traced step/transition functions
                body = runner_lib._chunk_body(
                    data, step_fn=step_fn, meta=meta,
                    device_sampling=device_sampling, transitions=True,
                    outer_fn=algo_t.outer_traced,
                    end_fn=algo_t.end_outer_traced, has_opre=has_opre,
                    has_opost=has_opost, has_end=has_end)
                return jax.lax.scan(body, carry_c, xs_c)[0]

            return jax.vmap(one_cell, in_axes=(0, xs_axes, 0))(
                carry, xs, cells)

        return functools.partial(jax.jit, donate_argnums=0)(exec_impl)

    return _shared_sweep_exec(cache_key, make)


def _make_sweep_record(template, build, cache_key):
    """Jitted batched record kernel: per-cell objectives (vmapped, with the
    cell's own traced prox/loss) + consensus into donated (records, cells)
    buffers at the carried slot."""
    from . import runner as runner_lib

    track = template.meta.track_consensus

    def make():
        def record_impl(bufs, params, data, cells):
            obj_buf, cons_buf, slot = bufs

            def one_cell(p, cell):
                algo_t, problem_t = _trace_build(build, cell)
                obj = runner_lib._resolved_objective(algo_t.meta, problem_t)
                return obj(p, data)

            with spans.scope(spans.RECORD):
                vals = jax.vmap(one_cell, in_axes=(0, 0))(params, cells)
                obj_buf = obj_buf.at[slot].set(vals)
                if track:
                    cons = jax.vmap(runner_lib.traceable_consensus)(params)
                    cons_buf = cons_buf.at[slot].set(cons)
            return (obj_buf, cons_buf, slot + 1)

        return functools.partial(jax.jit, donate_argnums=0)(record_impl)

    return _shared_sweep_exec(cache_key, make)


# ---------------------------------------------------------------------------
# The sweep driver
# ---------------------------------------------------------------------------

def _stack_states(states):
    return jax.tree.map(lambda *ls: jnp.stack(ls), *states)


def _cells_mesh(mesh, B: int):
    """Resolve the mesh + axis name ``shard="cells"`` splits the cell axis
    over: the caller's ``mesh`` (which must carry an axis named
    ``"cells"``), else a fresh 1-D ``("cells",)`` mesh over every visible
    device.  The grid size must split evenly over the axis (each device
    executes a contiguous grid slice)."""
    if mesh is None:
        ndev = len(jax.devices())
        mesh = mesh_lib.make_mesh((ndev,), ("cells",))
        axis, size = "cells", ndev
    else:
        size = dict(mesh.shape).get("cells")
        if size is None:
            raise ValueError(f"shard='cells' needs a mesh axis named "
                             f"'cells'; got {dict(mesh.shape)}")
        axis = "cells"
    if B % size != 0:
        raise ValueError(
            f"shard='cells': the {B}-cell grid must split evenly over the "
            f"'{axis}' mesh axis of size {size}; pad the grid (e.g. repeat "
            f"a seed) or pass a mesh whose cells axis divides it")
    return mesh, axis


def _mesh_collective(backend) -> bool:
    """Whether a transport mixes through mesh collectives of its own (the
    ``ppermute`` family, possibly wrapped) — those collectives claim the
    node axis and cannot nest inside a program whose mesh partitions the
    CELL axis."""
    if getattr(backend, "name", "") == "ppermute":
        return True
    inner = getattr(backend, "inner", None)
    if inner is None:
        return False
    if isinstance(inner, str):
        return inner == "ppermute"
    return _mesh_collective(inner)


def _cell_arrays(cells, axis_names) -> dict:
    return {name: np.stack([np.asarray(c[name]) for c in cells])
            for name in axis_names}


def run_sweep(build: Callable,
              grid: dict,
              schedule=None,
              exec: "ExecSpec | None" = None,
              *,
              seed: int = 0,
              record_every: int = 1,
              batched: "bool | None" = None,
              mode: str = "product",
              resident=UNSET,
              scan=UNSET,
              sampling=UNSET,
              gossip=UNSET,
              mesh=UNSET,
              kernel=UNSET) -> SweepResult:
    """Run ``build(**cell)`` over every cell of ``grid``.

    build:      cell factory ``build(**cell) -> (Algorithm, Problem)``;
                called once per cell with concrete values (validation +
                host planning) and once INSIDE the batched trace with
                traced values (vmapped cell axis).  Reuse the same callable
                across calls to keep compiled sweep executors warm.
    grid:       ``{axis: values}``; ``"seed"`` and ``"schedule"`` are
                driver-level axes (not passed to ``build``), everything
                else must be numeric.  ``mode="product"`` (default) takes
                the cartesian product, ``"zip"`` pairs the axes up.
    schedule:   the shared mixing schedule (or put a ``"schedule"`` axis in
                the grid for topology sweeps).
    exec:       an :class:`~repro.core.exec_spec.ExecSpec`; ``None``
                defaults to ``ExecSpec(resident=True)`` — the sweep is ONE
                batched device-resident program (single staged transfer,
                vmapped donated chunk executors, in-chunk outer
                transitions, one stacked history pull — O(1) transfers for
                the whole sweep).  ``resident=False`` drives the cells
                sequentially through the host/scan paths.  ``sampling``,
                ``gossip``, ``mesh``, ``kernel`` behave as on
                ``runner.run`` (all cells share one transport; with a
                ``"schedule"`` axis the wire representations must share
                static structure — ``gossip="dense"`` always batches;
                ``kernel`` swaps the fused Pallas resident step into the
                same vmapped executors).  ``shard="cells"`` partitions the
                batched program's CELL axis over a device mesh via GSPMD:
                staging, cell hyperparameters, donated state, and history
                buffers are placed with the cell axis split over the
                mesh's ``"cells"`` axis (the caller's ``mesh``, else a
                fresh 1-D mesh over all visible devices; the grid size
                must split evenly), so each device executes a contiguous
                grid slice — 100+-cell grids in one launch, histories
                equal to the unsharded batched program to float tolerance,
                with the O(1) transfer ledger intact.  Mesh-collective
                transports (``ppermute``) cannot combine with
                ``shard="cells"`` — their collectives claim the node axis.
    batched:    override the batching choice: ``exec.resident=True,
                batched=False`` runs the cells as SEQUENTIAL resident runs
                (the baseline the batched program is benchmarked against).
    resident, scan, sampling, gossip, mesh, kernel:
                DEPRECATED keyword spellings of the ExecSpec fields
                (one-release shim; combining them with ``exec=`` raises).
    """
    from . import runner as runner_lib

    # topology grids put the schedule in the grid, so the spec is the next
    # positional slot: run_sweep(build, grid, ExecSpec(...)) must not
    # silently swallow the spec as a schedule
    if isinstance(schedule, ExecSpec):
        if exec is not None:
            raise TypeError("run_sweep got two ExecSpecs — one in the "
                            "schedule slot and one as exec=")
        schedule, exec = None, schedule
    spec = exec_spec_lib.resolve_exec(
        exec, "runner.run_sweep", defaults={"resident": True},
        resident=resident, scan=scan, sampling=sampling, gossip=gossip,
        mesh=mesh, kernel=kernel)
    resident, sampling, kernel = spec.resident, spec.sampling, spec.kernel
    gossip, mesh, shard = spec.gossip, spec.mesh, spec.shard

    cells = expand_grid(grid, mode)
    B = len(cells)
    axis_names = [n for n in grid if n not in _RESERVED_AXES]
    seeds = [c.get("seed", seed) for c in cells]
    schedules = [c.get("schedule", schedule) for c in cells]
    if any(s is None for s in schedules):
        raise ValueError("run_sweep needs a schedule: pass schedule= or a "
                         "'schedule' grid axis")
    if batched is None:
        batched = resident
    if batched and not resident:
        raise ValueError("batched sweeps are device-resident by "
                         "construction; resident=False implies "
                         "batched=False")
    if shard == "nodes":
        raise ValueError("shard='nodes' partitions a single resident run's "
                         "node axis — use runner.run; batched sweeps "
                         "partition the CELL axis (shard='cells')")
    if shard == "cells" and not batched:
        raise ValueError("shard='cells' partitions the batched cell axis "
                         "over the mesh; it requires batched=True (the "
                         "default)")

    def build_cell_concrete(cell):
        out = build(**{k: v for k, v in cell.items()
                       if k not in _RESERVED_AXES})
        if not (isinstance(out, tuple) and len(out) == 2):
            raise TypeError("build(**cell) must return "
                            "(Algorithm, Problem), got "
                            f"{type(out).__name__}")
        return out

    built = [build_cell_concrete(c) for c in cells]
    _validate_cells(cells, built, schedules)
    template_algo, template_problem = built[0]
    meta0 = template_algo.meta

    if not batched:
        return _run_sequential(built, cells, schedules, seeds,
                               record_every=record_every, spec=spec)

    _require_traced(template_algo)

    # Under shard="cells" the mesh belongs to the CELL axis: the transport
    # must neither auto-select ppermute off it nor build node collectives
    # over it, so backends are resolved mesh-blind and mesh-collective
    # transports are rejected outright.
    gossip_mesh = None if shard == "cells" else mesh
    backend = runner_lib._resolved_backend(gossip, schedules[0], meta0,
                                           gossip_mesh)
    if shard == "cells" and _mesh_collective(backend):
        raise ValueError(
            f"shard='cells' partitions the CELL axis over the mesh, but the "
            f"{backend.name!r} transport mixes through node-axis mesh "
            f"collectives — the two claim the same devices.  Use "
            f"gossip='dense' or 'banded' (the mix stays within each "
            f"device's grid slice), or shard='nodes' on a single run")
    aux_by_sched: dict = {}
    auxes = []
    for s in schedules:
        aux = aux_by_sched.get(id(s))
        if aux is None:
            aux = aux_by_sched[id(s)] = backend.prepare(s, meta0,
                                                        mesh=gossip_mesh)
        auxes.append(aux)

    m = jax.tree.leaves(template_problem.x0)[0].shape[0]
    n = jax.tree.leaves(template_problem.full_data)[0].shape[1]
    param_count = transport.node_param_count(template_problem.x0)
    has_batch = meta0.batch_size > 0
    device_sampling = has_batch and sampling == "device"
    transfers = runner_lib._new_ledger()

    if has_batch and sampling == "host":
        pulled = runner_lib._leaves_on(template_problem.full_data, True)
        if pulled:
            runner_lib._moved(transfers, "d2h", pulled)
        host_data = jax.tree.map(np.asarray, template_problem.full_data)
    else:
        host_data = None

    rngs = [np.random.default_rng(s) for s in seeds]
    key_seeds = [int(r.integers(0, 2**31 - 1)) if device_sampling else 0
                 for r in rngs]

    plan_cells = [runner_lib._PlanCell(algo.meta, rng, backend, aux)
                  for (algo, _), rng, aux in zip(built, rngs, auxes)]
    plan = runner_lib._plan_resident(
        plan_cells, m=m, n=n, param_count=param_count,
        record_every=record_every, sampling=sampling, host_data=host_data,
        transitions=True, batched=True)

    # the kernel mode is part of the key: cells are rebuilt in-trace, so
    # no step-function identity distinguishes a fused program from an
    # unfused one — without it a kernel="pallas" sweep could be served a
    # cached "xla" executor (or vice versa)
    cache_key = ("sweep_exec", meta0.name, has_batch, sampling,
                 meta0.batch_size, build, tuple(axis_names),
                 plan.phi_batched, plan.opost_batched, kernel)
    exec_chunk = _make_sweep_exec(template_algo, build, sampling, plan,
                                  cache_key, kernel=kernel)
    record_kernel = _make_sweep_record(
        template_algo, build,
        ("sweep_record", meta0.name, meta0.track_consensus, build,
         tuple(axis_names)))

    # Under shard="cells" every batched array is PLACED at staging time:
    # per-cell leaves with the cell axis split over the mesh's "cells" axis
    # (each device holds — and executes — a contiguous grid slice), shared
    # leaves replicated.  The vmapped executors are elementwise along the
    # cell axis, so GSPMD partitions them with zero cross-device traffic
    # and the single-device program is recovered exactly per slice.
    if shard == "cells":
        smesh, caxis = _cells_mesh(mesh, B)
        NS, PS = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
        rep = NS(smesh, PS())
        cell0 = NS(smesh, PS(caxis))
        cell1 = NS(smesh, PS(None, caxis))
        comp_shard = [cell1 if a == 1 else rep
                      for a in _xs_axes(meta0, sampling, plan)]

        def _xs_shardings(xs):
            return tuple(jax.tree.map(lambda _, s=s: s, x)
                         for x, s in zip(xs, comp_shard))

        def _put_cells(tree, sharding):
            return jax.device_put(tree,
                                  jax.tree.map(lambda _: sharding, tree))
    else:
        _xs_shardings = None
        _put_cells = lambda tree, sharding: tree

    # one dataset staging (shared across cells) + ONE staging transfer for
    # every chunk's xs and the cell-axis hyperparameter arrays
    pushed = runner_lib._leaves_on(template_problem.full_data, False)
    if pushed:
        runner_lib._moved(transfers, "h2d", pushed)
    data_dev = jax.tree.map(jnp.asarray, template_problem.full_data)
    if shard == "cells":
        data_dev = _put_cells(data_dev, rep)
    runner_lib._warn_staging(runner_lib._staged_bytes(plan.chunks), cells=B)
    cell_arrays = _cell_arrays(cells, axis_names)
    runner_lib._moved(transfers, "h2d", ([c.xs for c in plan.chunks],
                                         cell_arrays))
    if shard == "cells":
        staged, cells_dev = jax.device_put(
            ([c.xs for c in plan.chunks], cell_arrays),
            ([_xs_shardings(c.xs) for c in plan.chunks],
             {name: cell0 for name in axis_names}))
    else:
        staged, cells_dev = jax.device_put(
            ([c.xs for c in plan.chunks], cell_arrays))

    states = []
    for (algo, _), aux in zip(built, auxes):
        state = algo.init()
        state = runner_lib.inject_mix_state(algo, backend, aux, state)
        if algo.device_state is not None:
            state = algo.device_state(state)
        states.append(state)
    state_b = runner_lib._shield_for_donation(_stack_states(states))
    if shard == "cells":
        state_b = _put_cells(state_b, cell0)

    if device_sampling:
        keys = jnp.stack([jax.random.PRNGKey(s) for s in key_seeds])
        if shard == "cells":
            keys = jax.device_put(keys, cell0)
        carry = (state_b, keys)
        unpack = lambda c: c[0]
    else:
        carry = state_b
        unpack = lambda c: c

    bufs = (jnp.zeros((plan.num_records, B), jnp.float32),
            jnp.zeros((plan.num_records, B), jnp.float32),
            jnp.zeros((), jnp.int32))
    if shard == "cells":
        # history buffers split along the cell column; the slot counter is
        # replicated so every shard advances it in lockstep
        bufs = (jax.device_put(bufs[0], cell1),
                jax.device_put(bufs[1], cell1),
                jax.device_put(bufs[2], rep))

    guard = runner_lib._RESIDENT_DISPATCH_GUARD
    get_params = template_algo.get_params
    for op in plan.ops:
        if op[0] == "chunk":
            with guard():
                carry = exec_chunk(carry, staged[op[1]], data_dev, cells_dev)
        else:  # ("record",)
            with guard():
                bufs = record_kernel(bufs, get_params(unpack(carry)),
                                     data_dev, cells_dev)

    runner_lib._moved(transfers, "d2h", bufs)
    objective, consensus, _ = jax.device_get(bufs)   # the ONE history pull

    history = runner_lib.RunHistory(
        objective=np.asarray(objective, np.float64),
        consensus=np.asarray(consensus, np.float64),
        epochs=plan.cols["epochs"],
        comm_rounds=plan.cols["comm_rounds"],
        steps=plan.cols["steps"])
    extras = {"wire_bytes": plan.wire, **runner_lib._ledger(transfers)}
    return SweepResult(grid=cells, params=get_params(unpack(carry)),
                       history=history, extras=extras)


def _run_sequential(built, cells, schedules, seeds, *, record_every,
                    spec: ExecSpec) -> SweepResult:
    """Reference path: one ``runner.run`` per cell, stacked to the same
    (records, cells) result shape as the batched program."""
    from . import runner as runner_lib

    results = []
    for (algo, problem), sched, s in zip(built, schedules, seeds):
        results.append(runner_lib.run(
            algo, problem, sched, spec, seed=s, record_every=record_every))
    lens = {len(r.history.steps) for r in results}
    if len(lens) > 1:
        raise _ragged(f"cells produced different record counts {lens}")
    history = runner_lib.RunHistory(
        **{f: np.stack([np.asarray(getattr(r.history, f))
                        for r in results], axis=1)
           for f in runner_lib.RunHistory._fields})
    extras = {
        "wire_bytes": np.stack(
            [np.asarray(r.extras["wire_bytes"]) for r in results], axis=1),
        **{k: sum(int(r.extras[k]) for r in results)
           for k in ("transfers_h2d", "transfers_d2h", "bytes_h2d",
                     "bytes_d2h")},
    }
    params = jax.tree.map(lambda *ls: jnp.stack(ls),
                          *[r.params for r in results])
    return SweepResult(grid=cells, params=params, history=history,
                       extras=extras)
