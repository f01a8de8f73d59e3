"""Batched resident sweep coverage: batched-vs-sequential history
equivalence for every registered algorithm (λ and seed axes traced through
the vmapped cell rebuild), ragged grids rejected with a clear error,
device-side outer transitions matching host ``outer``/``end_outer`` on
DPSVRG's growing K_s schedule, O(1) transfers for a whole sweep (ledger AND
an XLA transfer-guard over every dispatch), topology (schedule-axis) grids,
the batch-aware staging warning, and ``reset_executable_caches`` clearing
the vmapped sweep executors."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (algorithm, dpsvrg, gossip, graphs, inexact, prox,
                        runner, sweep)
from repro.data import synthetic
from repro.core.exec_spec import ExecSpec


def logreg_loss(w, batch):
    logits = batch["features"] @ w
    y = batch["labels"]
    return jnp.mean(-y * logits + jnp.log1p(jnp.exp(logits)))


@functools.lru_cache(maxsize=None)
def _setup(m=4, n=128, d=12, seed=0):
    ds = synthetic.make_classification(n=n, d=d, seed=seed)
    data = {k: jnp.asarray(v)
            for k, v in synthetic.partition_per_node(ds, m).items()}
    x0 = gossip.stack_tree(jnp.zeros(d), m)
    return data, x0


def _sched(m=4, b=2, seed=0):
    return graphs.b_connected_ring_schedule(m, b=b, seed=seed)


def _build(name):
    """Cell factory for ``name`` with a λ axis (traced through the prox)."""
    data, x0 = _setup()

    def build(lam=0.01):
        problem = algorithm.Problem(logreg_loss, prox.l1(lam), x0, data)
        if name == "dpsvrg":
            algo = algorithm.dpsvrg_algorithm(
                problem, dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3,
                                                  num_outer=4))
        elif name == "dspg":
            algo = algorithm.dspg_algorithm(
                problem, dpsvrg.DSPGHyperParams(alpha0=0.3), 37)
        elif name == "dpg":
            algo = algorithm.dpg_algorithm(problem, 0.3, 12)
        elif name == "gt_svrg":
            algo = algorithm.gt_svrg_algorithm(problem, 0.1, 3, 8)
        elif name == "loopless_dpsvrg":
            algo = algorithm.loopless_dpsvrg_algorithm(
                problem, 0.3, 33, snapshot_prob=0.25)
        else:
            raise KeyError(name)
        return algo, problem

    return build


def _assert_sweeps_agree(a, b):
    for field in ("epochs", "comm_rounds", "steps"):
        np.testing.assert_array_equal(getattr(a.history, field),
                                      getattr(b.history, field),
                                      err_msg=field)
    np.testing.assert_allclose(a.history.objective, b.history.objective,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(a.history.consensus, b.history.consensus,
                               rtol=1e-3, atol=1e-6)
    np.testing.assert_array_equal(a.extras["wire_bytes"],
                                  b.extras["wire_bytes"])


# ---------------------------------------------------------------------------
# batched vs sequential equivalence, every registered algorithm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name", ["dpsvrg", "dspg", "dpg", "gt_svrg", "loopless_dpsvrg"])
def test_batched_matches_sequential(name):
    build = _build(name)
    grid = {"lam": [0.001, 0.1], "seed": [3, 7]}
    batched = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=4)
    sequential = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=4, batched=False)
    assert batched.history.objective.shape[1] == 4
    _assert_sweeps_agree(batched, sequential)
    np.testing.assert_allclose(np.asarray(batched.params),
                               np.asarray(sequential.params),
                               rtol=1e-4, atol=1e-6)


def test_batched_matches_sequential_inexact_prox_svrg():
    """The sixth registered algorithm: Algorithm 2 on one virtual node."""
    data, _ = _setup()
    flat = {k: v.reshape(1, -1, *v.shape[2:]) for k, v in data.items()}
    x0 = gossip.stack_tree(jnp.zeros(12), 1)
    sched = graphs.static_schedule(np.eye(1), name="centralized")

    def build(lam=0.01):
        problem = algorithm.Problem(logreg_loss, prox.l1(lam), x0, flat)
        hp = inexact.InexactHyperParams(alpha=0.3, beta=1.2, n0=3,
                                        num_outer=3)
        return algorithm.ALGORITHMS["inexact_prox_svrg"](problem, hp), \
            problem

    grid = {"lam": [0.001, 0.1], "seed": [0, 2]}
    batched = sweep.run_sweep(build, grid, sched, exec=ExecSpec(resident=True, gossip="dense"), record_every=2)
    sequential = sweep.run_sweep(build, grid, sched, exec=ExecSpec(resident=True, gossip="dense"), record_every=2, batched=False)
    _assert_sweeps_agree(batched, sequential)


def test_batched_matches_sequential_host_path():
    """The sequential comparator can also drive the HOST path — the batched
    program agrees with the slowest, most-trusted reference too."""
    build = _build("dspg")
    grid = {"seed": [0, 1, 2]}
    batched = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=8)
    host = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=False, gossip="dense"), record_every=8, batched=False)
    _assert_sweeps_agree(batched, host)


def test_sweep_cell_slicing_matches_plain_run():
    """SweepResult.cell(i) is the same RunResult a plain runner.run of that
    cell produces."""
    build = _build("dpsvrg")
    res = sweep.run_sweep(build, {"seed": [5, 9]}, _sched(), exec=ExecSpec(resident=True, gossip="dense"),
                          record_every=0)
    algo, problem = build()
    ref = runner.run(algo, problem, _sched(), exec=ExecSpec(gossip="dense"), seed=9, record_every=0)
    cell = res.cell(1)
    np.testing.assert_allclose(cell.history.objective, ref.history.objective,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(cell.history.epochs, ref.history.epochs)
    np.testing.assert_array_equal(cell.extras["wire_bytes"],
                                  ref.extras["wire_bytes"])


def test_schedule_axis_zip_topology_grid():
    """Fig-5 shape: cells gossip over DIFFERENT time-varying schedules
    (zip-paired with per-cell seeds) inside one batched dense program."""
    build = _build("dpsvrg")
    scheds = [_sched(b=1, seed=1), _sched(b=3, seed=3)]
    grid = {"schedule": scheds, "seed": [1, 3]}
    batched = sweep.run_sweep(build, grid, exec=ExecSpec(resident=True, gossip="dense"), record_every=0,
                              mode="zip")
    sequential = sweep.run_sweep(build, grid, exec=ExecSpec(resident=True, gossip="dense"), record_every=0, mode="zip", batched=False)
    _assert_sweeps_agree(batched, sequential)
    assert batched.extras["transfers_h2d"] <= 2


def test_device_sampling_sweep_reproducible():
    build = _build("dspg")
    grid = {"lam": [0.01, 0.03], "seed": [0, 1]}
    a = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, sampling="device", gossip="dense"), record_every=10)
    b = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, sampling="device", gossip="dense"), record_every=10)
    np.testing.assert_array_equal(a.history.objective, b.history.objective)
    # the lightly-regularized cells descend
    assert a.history.objective[-1, 0] < a.history.objective[0, 0]


# ---------------------------------------------------------------------------
# ragged grids rejected with a clear error
# ---------------------------------------------------------------------------

def test_ragged_grid_structural_axis_rejected():
    """An axis that changes the loop structure (num_steps) is not
    batchable and must say so."""
    data, x0 = _setup()

    def build(steps=20):
        problem = algorithm.Problem(logreg_loss, prox.l1(0.01), x0, data)
        return algorithm.dspg_algorithm(
            problem, dpsvrg.DSPGHyperParams(alpha0=0.3), steps), problem

    with pytest.raises(ValueError, match="ragged sweep grid.*num_steps"):
        sweep.run_sweep(build, {"steps": [20, 40]}, _sched())


def test_ragged_grid_different_dataset_rejected():
    data, x0 = _setup()
    other = {k: v + 1.0 for k, v in data.items()}

    def build(which=0):
        d = data if which == 0 else other
        problem = algorithm.Problem(logreg_loss, prox.l1(0.01), x0, d)
        return algorithm.dspg_algorithm(
            problem, dpsvrg.DSPGHyperParams(alpha0=0.3), 10), problem

    with pytest.raises(ValueError, match="ragged sweep grid.*dataset"):
        sweep.run_sweep(build, {"which": [0, 1]}, _sched())


def test_ragged_grid_mixed_schedule_structure_needs_dense():
    """Banded wire formats with different offset unions cannot share one
    batched program; the error points at gossip='dense'."""
    build = _build("dspg")
    # identity gossip decomposes into the {0} band; the ring needs {0,1,3}
    scheds = [graphs.static_schedule(np.eye(4), name="identity4"),
              _sched(b=1, seed=2)]
    with pytest.raises(ValueError, match="dense"):
        sweep.run_sweep(build, {"schedule": scheds, "seed": [0, 1]}, exec=ExecSpec(resident=True, gossip="banded"), mode="zip")
    # the same grid batches fine on the structure-free dense wire format
    res = sweep.run_sweep(build, {"schedule": scheds, "seed": [0, 1]}, exec=ExecSpec(resident=True, gossip="dense"), mode="zip", record_every=5)
    assert res.history.objective.shape[1] == 2


def test_zip_mode_length_mismatch_rejected():
    build = _build("dspg")
    with pytest.raises(ValueError, match="zip-mode"):
        sweep.run_sweep(build, {"lam": [0.01, 0.1], "seed": [0]},
                        _sched(), mode="zip")


def test_empty_grid_rejected():
    with pytest.raises(ValueError, match="empty sweep grid"):
        sweep.run_sweep(_build("dspg"), {}, _sched())


# ---------------------------------------------------------------------------
# device-side outer transitions vs host outer/end_outer
# ---------------------------------------------------------------------------

def test_device_transitions_match_host_dispatch_on_growing_ks():
    """DPSVRG's growing K_s rounds: folding outer/end_outer into the
    compiled chunks (lax.cond on the round schedule) reproduces the
    host-dispatched transitions to float precision, for both record
    cadences that interact with round boundaries."""
    build = _build("dpsvrg")
    algo_factory = lambda: build()[0]
    _, problem = build()
    for record_every in (0, 5):
        host_side = runner.run(algo_factory(), problem, _sched(), exec=ExecSpec(resident=True, device_transitions=False, gossip="dense"), seed=3,
                               record_every=record_every)
        device_side = runner.run(algo_factory(), problem, _sched(), exec=ExecSpec(resident=True, device_transitions=True, gossip="dense"), seed=3,
                                 record_every=record_every)
        np.testing.assert_array_equal(host_side.history.steps,
                                      device_side.history.steps)
        np.testing.assert_allclose(host_side.history.objective,
                                   device_side.history.objective,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(host_side.history.consensus,
                                   device_side.history.consensus,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(host_side.params),
                                   np.asarray(device_side.params),
                                   rtol=1e-6, atol=1e-7)


def test_device_transitions_requires_contract():
    """device_transitions=True on an algorithm without the traced contract
    raises instead of silently falling back."""
    import dataclasses
    build = _build("dpsvrg")
    algo, problem = build()
    stripped = dataclasses.replace(algo, outer_traced=None,
                                   end_outer_traced=None)
    with pytest.raises(ValueError, match="outer_traced"):
        runner.run(stripped, problem, _sched(), exec=ExecSpec(resident=True, device_transitions=True))
    # auto falls back to host dispatches and still matches
    res = runner.run(stripped, problem, _sched(), exec=ExecSpec(resident=True, gossip="dense"), seed=3, record_every=5)
    ref = runner.run(build()[0], problem, _sched(), exec=ExecSpec(resident=True, gossip="dense"), seed=3, record_every=5)
    np.testing.assert_allclose(res.history.objective, ref.history.objective,
                               rtol=1e-6, atol=1e-7)


def test_loopless_coin_flip_transitions_in_chunk():
    """Loopless coin-flip snapshots fold into the chunk body (no chunk
    cuts): resident histories still match the host loop's rng stream."""
    build = _build("loopless_dpsvrg")
    algo, problem = build()
    host = runner.run(build()[0], problem, _sched(), exec=ExecSpec(gossip="dense"), seed=11,
                      record_every=8)
    res = runner.run(build()[0], problem, _sched(), exec=ExecSpec(resident=True, gossip="dense"), seed=11,
                     record_every=8)
    np.testing.assert_allclose(host.history.objective, res.history.objective,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(host.history.epochs, res.history.epochs)


# ---------------------------------------------------------------------------
# O(1) transfers for the whole sweep
# ---------------------------------------------------------------------------

def test_sweep_transfer_ledger_is_o1():
    build = _build("dpsvrg")
    grid = {"lam": [0.001, 0.01, 0.03, 0.1], "seed": [0, 1]}
    batched = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=0)
    sequential = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=0, batched=False)
    # whole 8-cell sweep: one xs+cells staging put, one history pull (+ the
    # host-side dataset copy)
    assert batched.extras["transfers_h2d"] == 1
    assert batched.extras["transfers_d2h"] <= 2
    # the per-cell sequential baseline pays per cell
    assert sequential.extras["transfers_h2d"] >= len(batched.grid)


def test_sweep_dispatch_is_transfer_free_under_xla_guard():
    """Every chunk/record dispatch of a FULL batched sweep runs under
    ``jax.transfer_guard("disallow")``: XLA faults on any implicit
    host<->device transfer, so the O(1) claim holds at the runtime level,
    not just in the ledger."""
    build = _build("dpsvrg")
    grid = {"lam": [0.001, 0.1], "seed": [0, 1]}
    old = runner._RESIDENT_DISPATCH_GUARD
    runner._RESIDENT_DISPATCH_GUARD = \
        lambda: jax.transfer_guard("disallow")
    try:
        res = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=0)
    finally:
        runner._RESIDENT_DISPATCH_GUARD = old
    # the lightly-regularized cells descend (λ=0.1 cells stay near x=0)
    assert np.all(res.history.objective[-1, :2]
                  < res.history.objective[0, :2])


# ---------------------------------------------------------------------------
# staging warning + executor cache hygiene
# ---------------------------------------------------------------------------

def test_staging_warning_accounts_batch_axis():
    """The staged-bytes warning fires on the sweep TOTAL (cells included in
    the message), and the batched plan's staged bytes actually scale with
    the cell axis."""
    with pytest.warns(RuntimeWarning, match="8 sweep cells"):
        runner._warn_staging(2 << 30, cells=8)
    with pytest.warns(RuntimeWarning, match="resident staging"):
        runner._warn_staging(2 << 30)

    build = _build("dspg")
    data, _ = _setup()
    m = 4
    n = jax.tree.leaves(data)[0].shape[1]
    host_data = jax.tree.map(np.asarray, data)

    def plan_for(cells):
        algo, _ = build()
        backend = runner.transport.GOSSIP_BACKENDS["dense"]
        aux = backend.prepare(_sched(), algo.meta)
        plan_cells = [runner._PlanCell(algo.meta,
                                       np.random.default_rng(i), backend,
                                       aux) for i in range(cells)]
        return runner._plan_resident(
            plan_cells, m=m, n=n, param_count=12, record_every=10,
            sampling="host", host_data=host_data, transitions=True,
            batched=cells > 1)

    single, batched = plan_for(1), plan_for(4)
    # both stage one run-level array per xs leaf, rows for every chunk
    assert len(jax.tree.leaves(single.xs)) == \
        len(jax.tree.leaves(batched.xs))
    assert jax.tree.leaves(single.xs)[0].shape[0] == sum(single.lengths)
    assert runner._nbytes(batched.xs) > 3 * runner._nbytes(single.xs)
    # total bytes, not per cell

    # a batched sweep's staged buffers do not grow with its chunk count
    grid = {"seed": [0, 1]}
    few = sweep.run_sweep(build, grid, _sched(),
                          ExecSpec(resident=True, gossip="dense"),
                          record_every=10)
    many = sweep.run_sweep(build, grid, _sched(),
                           ExecSpec(resident=True, gossip="dense"),
                           record_every=3)
    assert many.history.steps.shape[0] > few.history.steps.shape[0]
    assert many.extras["staged_buffers"] == few.extras["staged_buffers"]


def test_reset_executable_caches_clears_sweep_executors():
    build = _build("dspg")
    grid = {"seed": [0, 1]}
    sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=10)
    assert any(k and k[0] in ("sweep_exec", "sweep_record")
               for k in sweep._SWEEP_EXEC_CACHE), \
        "vmapped sweep executors should be cached"
    runner.reset_executable_caches()
    assert not sweep._SWEEP_EXEC_CACHE
    assert not runner._EXEC_CACHE
    # a fresh sweep after the reset still works (recompiles)
    res = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, gossip="dense"), record_every=10)
    assert res.history.objective.shape[1] == 2


# ---------------------------------------------------------------------------
# fused-kernel batched sweeps (kernel="pallas"/"auto")
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["pallas", "auto"])
def test_sweep_kernel_matches_sequential(kernel):
    """The fused resident step swaps into the vmapped sweep executors
    (resolved per cell inside the trace) without changing the plan or the
    staging; batched histories must match the sequential resident runs
    driven through the same kernel knob."""
    build = _build("dpsvrg")
    grid = {"lam": [0.001, 0.1], "seed": [3, 7]}
    batched = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, kernel=kernel, gossip="dense"), record_every=4)
    sequential = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, kernel=kernel, gossip="dense"), record_every=4, batched=False)
    _assert_sweeps_agree(batched, sequential)
    assert batched.extras["transfers_h2d"] == 1


def test_sweep_kernel_mode_is_part_of_executor_cache_key():
    """Cells are rebuilt in-trace, so no step identity distinguishes fused
    from unfused sweep executors — the kernel mode itself must key the
    cache, and 'auto' at small d must serve histories bit-identical to
    'xla' (the fallback picks the base step at trace time)."""
    build = _build("dspg")
    grid = {"lam": [0.01, 0.1], "seed": [0, 1]}
    xla = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, kernel="xla", gossip="dense"), record_every=5)
    pallas = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, kernel="pallas", gossip="dense"), record_every=5)
    auto = sweep.run_sweep(build, grid, _sched(), exec=ExecSpec(resident=True, kernel="auto", gossip="dense"), record_every=5)
    modes = {k[-1] for k in sweep._SWEEP_EXEC_CACHE if k[0] == "sweep_exec"}
    assert {"xla", "pallas", "auto"} <= modes
    np.testing.assert_array_equal(auto.history.objective,
                                  xla.history.objective)
    np.testing.assert_allclose(pallas.history.objective,
                               xla.history.objective, rtol=1e-4, atol=1e-6)


def test_sweep_kernel_requires_resident():
    build = _build("dspg")
    with pytest.raises(ValueError, match="resident"):
        sweep.run_sweep(build, {"seed": [0]}, _sched(), exec=ExecSpec(resident=False, kernel="pallas"),
                        batched=False)
