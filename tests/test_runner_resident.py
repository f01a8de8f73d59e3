"""Device-resident runner coverage: host/scan/resident history equivalence
across every registered algorithm, donated-carry in-place updates (no copy of
the stacked state in the compiled HLO), O(1) host<->device transfers per run
(ledger counts AND an XLA transfer-guard over the dispatch hot path), in-scan
device sampling (same convergence envelope, different stream), the AlgoMeta
``resident_objective`` contract, and the dtype-preserving wire stacking."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (algorithm, compression, dpsvrg, gossip, graphs,
                        inexact, prox, runner)
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
    h = prox.l1(0.01)
    x0 = gossip.stack_tree(jnp.zeros(d), m)
    return data, h, x0


def _problem(data, h, x0):
    return algorithm.Problem(logreg_loss, h, x0, data)


def _sched(m=4):
    return graphs.b_connected_ring_schedule(m, b=2, seed=0)


def _build(name, problem):
    if name == "dpsvrg":
        return algorithm.ALGORITHMS[name](
            problem, dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3,
                                              num_outer=4))
    if name == "dspg":
        return algorithm.ALGORITHMS[name](
            problem, dpsvrg.DSPGHyperParams(alpha0=0.3), 37)
    if name == "dpg":
        return algorithm.ALGORITHMS[name](problem, 0.3, 12)
    if name == "gt_svrg":
        return algorithm.ALGORITHMS[name](problem, 0.1, 3, 8)
    if name == "loopless_dpsvrg":
        return algorithm.ALGORITHMS[name](problem, 0.3, 33,
                                          snapshot_prob=0.25)
    raise KeyError(name)


def _assert_agrees(a, b):
    for field in ("epochs", "comm_rounds", "steps"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field),
                                      err_msg=field)
    np.testing.assert_allclose(a.objective, b.objective, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(a.consensus, b.consensus, rtol=1e-3, atol=1e-6)


# ---------------------------------------------------------------------------
# host / scan / resident equivalence, every registered algorithm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name", ["dpsvrg", "dspg", "dpg", "gt_svrg", "loopless_dpsvrg"])
def test_resident_matches_host_and_scan(name):
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    runs = {}
    for mode in ("host", "scan", "resident"):
        algo = _build(name, problem)
        runs[mode] = runner.run(
            algo, problem, sched, exec=ExecSpec(scan=(mode == "scan"), resident=(mode == "resident"), gossip="dense"), seed=3, record_every=5).history
    _assert_agrees(runs["host"], runs["scan"])
    _assert_agrees(runs["host"], runs["resident"])


def test_resident_matches_host_inexact_prox_svrg():
    """Algorithm 2 (m = 1 virtual node, identity gossip) through the
    resident path — the sixth registered algorithm."""
    data, h, _ = _setup()
    flat = {k: v.reshape(1, -1, *v.shape[2:]) for k, v in data.items()}
    x0 = gossip.stack_tree(jnp.zeros(12), 1)
    problem = algorithm.Problem(logreg_loss, h, x0, flat)
    sched = graphs.static_schedule(np.eye(1), name="centralized")
    hp = inexact.InexactHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3)
    host = runner.run(algorithm.ALGORITHMS["inexact_prox_svrg"](problem, hp),
                      problem, sched, exec=ExecSpec(gossip="dense"), seed=0, record_every=2).history
    res = runner.run(algorithm.ALGORITHMS["inexact_prox_svrg"](problem, hp),
                     problem, sched, exec=ExecSpec(resident=True, gossip="dense"), seed=0, record_every=2).history
    _assert_agrees(host, res)


def test_resident_matches_host_on_banded_transport():
    """Resident chunks stage BandedPhi xs like the scan path does."""
    data, h, x0 = _setup()
    mats = graphs.edge_matching_matrices(4)
    sched = graphs.MixingSchedule(tuple(mats), b=len(mats), eta=0.5,
                                  name="matching4")
    problem = _problem(data, h, x0)
    host = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(gossip="dense"), seed=2,
                      record_every=8).history
    res = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, gossip="banded"), seed=2,
                     record_every=8).history
    _assert_agrees(host, res)


def test_resident_matches_host_compressed_transport():
    """The stateful compressed transport's error-feedback state rides the
    donated resident carry."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.2, beta=1.2, n0=3, num_outer=3,
                                  k_max=2)
    host = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                      sched, exec=ExecSpec(gossip="compressed"), seed=1, record_every=4).history
    res = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                     sched, exec=ExecSpec(resident=True, gossip="compressed"), seed=1, record_every=4).history
    _assert_agrees(host, res)


def test_resident_record_every_zero_outer_rounds():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4)
    host = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                      sched, exec=ExecSpec(gossip="dense"), seed=0, record_every=0).history
    res = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                     sched, exec=ExecSpec(resident=True, gossip="dense"), seed=0, record_every=0).history
    _assert_agrees(host, res)


# ---------------------------------------------------------------------------
# donated carries: in-place update, no stacked-state copy
# ---------------------------------------------------------------------------

def test_resident_exec_donates_state():
    """The compiled chunk aliases the donated carry into its output
    (input_output_alias in the HLO — the stacked iterate is updated in
    place, not copied) and the input buffers are invalidated after the
    call."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = _build("dspg", problem)
    exec_chunk = runner._make_resident_exec(algo, "host")

    L, m, d = 4, 4, 12
    state = jax.tree.map(lambda a: jnp.array(a, copy=True), algo.init())
    carry = (state, jnp.zeros((), jnp.int32))
    # run-level xs of two chunks; this chunk reads the first L rows
    batch = {"features": np.zeros((2 * L, m, 1, d), np.float32),
             "labels": np.zeros((2 * L, m, 1), np.float32)}
    xs, shapes = runner._stage_rows(
        (batch, np.stack([np.eye(m)] * 2 * L),
         np.ones(2 * L, np.float32), np.ones(2 * L, bool)), (False,) * 4)
    compiled = exec_chunk.lower(carry, xs, data, L, shapes).compile()
    assert "input_output_alias" in compiled.as_text()

    out, offset = exec_chunk(carry, xs, data, L, shapes)
    assert state.params.is_deleted()          # donated, not copied
    assert not out.params.is_deleted()
    assert int(offset) == L                   # the next chunk's first row


def test_resident_run_shields_caller_buffers():
    """Donation must never invalidate problem.x0 (the init state references
    it): two consecutive resident runs from the same Problem agree."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    r1 = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True), seed=2,
                    record_every=8).history
    r2 = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True), seed=2,
                    record_every=8).history
    np.testing.assert_array_equal(r1.objective, r2.objective)
    assert not x0.is_deleted()


# ---------------------------------------------------------------------------
# O(1) transfers per run
# ---------------------------------------------------------------------------

def test_resident_transfer_ledger_is_o1():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    res = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True), seed=0,
                     record_every=5)
    scan = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(scan=True), seed=0,
                      record_every=5)
    # resident: one staging put + one host dataset copy + one history pull
    assert res.extras["transfers_h2d"] == 1
    assert res.extras["transfers_d2h"] <= 2
    # the scan path pays per chunk and per record
    assert scan.extras["transfers_h2d"] >= 8   # ~#chunks
    assert scan.extras["transfers_d2h"] >= 8   # ~2 x #records


def test_resident_dispatch_is_transfer_free_under_xla_guard():
    """Run a resident DSPG with every chunk/record dispatch wrapped in
    ``jax.transfer_guard("disallow")``: XLA itself faults on ANY implicit
    host<->device transfer during the compiled hot path, so this is the
    strongest form of the O(1)-transfers claim (staging and the final pull
    happen outside the guarded dispatches, via explicit device_put/get)."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    old = runner._RESIDENT_DISPATCH_GUARD
    runner._RESIDENT_DISPATCH_GUARD = lambda: jax.transfer_guard("disallow")
    try:
        res = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True), seed=0,
                         record_every=5)
    finally:
        runner._RESIDENT_DISPATCH_GUARD = old
    assert res.history.objective[-1] < res.history.objective[0]


# ---------------------------------------------------------------------------
# run-level staging: one buffer per xs leaf, however many chunks
# ---------------------------------------------------------------------------

def _staging_dpsvrg(problem, num_outer):
    return algorithm.ALGORITHMS["dpsvrg"](
        problem, dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3,
                                          num_outer=num_outer))


def _digest(res) -> str:
    """The bytes of a run's objective and consensus history and its final
    params."""
    h = hashlib.sha256()
    for a in (res.history.objective, res.history.consensus,
              *jax.tree.leaves(res.params)):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


# the digests the per-chunk staging (one buffer per leaf per chunk) gave
# for these jobs; staging one buffer per leaf for the whole run must not
# change a bit of them
_PER_CHUNK_DIGESTS = {
    ("host", True): "59fb876a6022823e",
    ("host", False): "d6354dcad1c96a01",
    ("device", True): "5f92dd9bc64f02e7",
    ("device", False): "93deb3ea3619291b",
}


@pytest.mark.parametrize("transitions", [True, False])
@pytest.mark.parametrize("sampling", ["host", "device"])
def test_resident_stages_one_buffer_per_leaf(sampling, transitions):
    """A DPSVRG job whose chunks take three bucket lengths stages its xs as
    one buffer per leaf: ``staged_buffers`` is the xs leaf count, the same
    for a job with twice the outer rounds (twice the chunks), and the
    history and params are bitwise those of per-chunk staging.  Every
    dispatch runs under the XLA transfer guard: the chunk offset lives on
    the device."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    spec = ExecSpec(resident=True, sampling=sampling,
                    device_transitions=transitions, gossip="dense")
    algo = _staging_dpsvrg(problem, 4)
    backend = runner.transport.GOSSIP_BACKENDS["dense"]
    plan = runner._plan_resident(
        [runner._PlanCell(algo.meta, np.random.default_rng(0), backend,
                          backend.prepare(sched, algo.meta))],
        m=4, n=jax.tree.leaves(data)[0].shape[1], param_count=12,
        record_every=3, sampling=sampling,
        host_data=jax.tree.map(np.asarray, data), transitions=transitions)
    assert len(set(plan.lengths)) >= 3
    leaves = len(jax.tree.leaves(plan.xs))
    # batch features and labels under host sampling, phi, alpha, keep, and
    # the four transition flags
    assert leaves == (2 if sampling == "host" else 0) + 3 + \
        (4 if transitions else 0)

    old = runner._RESIDENT_DISPATCH_GUARD
    runner._RESIDENT_DISPATCH_GUARD = lambda: jax.transfer_guard("disallow")
    try:
        res = runner.run(algo, problem, sched, spec, seed=5, record_every=3)
        longer = runner.run(_staging_dpsvrg(problem, 8), problem, sched,
                            spec, seed=5, record_every=3)
    finally:
        runner._RESIDENT_DISPATCH_GUARD = old
    assert res.extras["staged_buffers"] == leaves
    assert longer.extras["staged_buffers"] == leaves
    assert res.extras["transfers_h2d"] == 1
    assert _digest(res) == _PER_CHUNK_DIGESTS[sampling, transitions]


# ---------------------------------------------------------------------------
# in-scan device sampling
# ---------------------------------------------------------------------------

def test_device_sampling_same_envelope_different_stream():
    """sampling="device" draws a different (jax.random) sample stream, so
    the trajectory differs from the host stream — but it solves the same
    problem: the final objective lands in the same convergence envelope."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    host = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, sampling="host"), seed=0,
                      record_every=10).history
    dev = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, sampling="device"), seed=0,
                     record_every=10).history
    # different stream: trajectories are not identical
    assert not np.allclose(host.objective[1:], dev.objective[1:])
    # same envelope: both descend, final gaps within a third of the total
    # descent of each other
    descent = host.objective[0] - host.objective[-1]
    assert descent > 0
    assert dev.objective[-1] < dev.objective[0]
    assert abs(dev.objective[-1] - host.objective[-1]) < descent / 3
    # reproducible from the seed
    dev2 = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, sampling="device"), seed=0,
                      record_every=10).history
    np.testing.assert_array_equal(dev.objective, dev2.objective)


def test_device_sampling_requires_resident():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    with pytest.raises(ValueError):
        runner.run(_build("dspg", problem), problem, _sched(), exec=ExecSpec(sampling="device"))
    with pytest.raises(ValueError):
        runner.run(_build("dspg", problem), problem, _sched(), exec=ExecSpec(sampling="banana"))


# ---------------------------------------------------------------------------
# AlgoMeta resident contract + guard rails
# ---------------------------------------------------------------------------

def test_resident_objective_contract_overrides_default():
    """AlgoMeta.resident_objective is the traceable objective the on-device
    record kernel evaluates."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = _build("dspg", problem)
    meta = dataclasses.replace(
        algo.meta,
        resident_objective=lambda params, full_data: jnp.float32(42.0))
    algo = dataclasses.replace(algo, meta=meta)
    res = runner.run(algo, problem, _sched(), exec=ExecSpec(resident=True), seed=0, record_every=10)
    np.testing.assert_allclose(res.history.objective, 42.0)


def test_resident_rejects_host_extra_metrics():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    with pytest.raises(ValueError):
        runner.run(_build("dspg", problem), problem, _sched(), exec=ExecSpec(resident=True),
                   extra_metrics={"max": lambda p: float(jnp.max(p))})


# ---------------------------------------------------------------------------
# dtype-preserving wire stacking (scan xs)
# ---------------------------------------------------------------------------

def test_stack_phis_preserves_integer_payload_dtype():
    """8-bit quantized payload leaves must NOT silently widen to f32 when
    stacked into scan xs (the historical force-cast quadrupled the staged
    bytes and destroyed integer wire payloads); float leaves still
    canonicalize to f32."""
    payload = [compression.CompressedPhi(
        np.arange(16, dtype=np.int8).reshape(4, 4), bits=8)
        for _ in range(3)]
    stacked = runner._stack_phis(payload)
    assert stacked.inner.dtype == jnp.int8
    assert stacked.inner.shape == (3, 4, 4)
    assert stacked.bits == 8

    dense = [np.eye(4, dtype=np.float64) for _ in range(3)]
    assert runner._stack_phis(dense).dtype == jnp.float32

    banded = [gossip.BandedPhi((0, 1), np.ones((2, 4), np.float32))
              for _ in range(3)]
    st = runner._stack_phis(banded)
    assert st.coeffs.dtype == jnp.float32
    assert st.coeffs.shape == (3, 2, 4)


def test_resident_executor_cache_persists_across_instances():
    """Rebuilding the algorithm (as sweeps do per point) reuses the SAME
    resident executor object — compiled chunks survive run() calls."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    e1 = runner._make_resident_exec(_build("dspg", problem), "host")
    e2 = runner._make_resident_exec(_build("dspg", problem), "host")
    assert e1 is e2


# ---------------------------------------------------------------------------
# fused-kernel resident path (kernel="pallas"/"auto")
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["pallas", "auto"])
@pytest.mark.parametrize(
    "name", ["dpsvrg", "dspg", "dpg", "gt_svrg", "loopless_dpsvrg"])
def test_resident_kernel_matches_host(name, kernel):
    """Swapping the fused resident step in (kernel='pallas') — or letting
    'auto' choose per shape — reproduces the host loop's history to the
    same tolerance the plain resident path is held to, for EVERY
    registered algorithm (the ones without a fused twin or with a fused
    fallback keep their base step and must be unaffected)."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    host = runner.run(_build(name, problem), problem, sched, exec=ExecSpec(gossip="dense"), seed=3,
                      record_every=5).history
    res = runner.run(_build(name, problem), problem, sched, exec=ExecSpec(resident=True, kernel=kernel, gossip="dense"), seed=3,
                     record_every=5).history
    _assert_agrees(host, res)


def test_resident_kernel_matches_on_banded_transport():
    """The fused step lowers BandedPhi wire payloads to a dense mix matrix
    in-trace (gossip.banded_to_dense) — histories must agree with the host
    loop's roll-based banded mixing."""
    data, h, x0 = _setup()
    mats = graphs.edge_matching_matrices(4)
    sched = graphs.MixingSchedule(tuple(mats), b=len(mats), eta=0.5,
                                  name="matching4")
    problem = _problem(data, h, x0)
    host = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(gossip="dense"), seed=2,
                      record_every=8).history
    res = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, kernel="pallas", gossip="banded"), seed=2,
                     record_every=8).history
    _assert_agrees(host, res)


def test_resident_kernel_auto_small_d_is_bitwise_unfused():
    """Below FUSED_MIN_D per-node parameters, kernel='auto' resolves to the
    base step at trace time — histories are bit-identical to kernel='xla',
    not merely close."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    xla = runner.run(_build("dpsvrg", problem), problem, sched, exec=ExecSpec(resident=True, kernel="xla", gossip="dense"), seed=1,
                     record_every=5).history
    auto = runner.run(_build("dpsvrg", problem), problem, sched, exec=ExecSpec(resident=True, kernel="auto", gossip="dense"), seed=1,
                      record_every=5).history
    np.testing.assert_array_equal(xla.objective, auto.objective)
    np.testing.assert_array_equal(xla.consensus, auto.consensus)


def test_resident_kernel_exec_donates_state():
    """The fused-step executor keeps the donation contract: the compiled
    chunk aliases the donated carry into its output (input_output_alias in
    the HLO) and invalidates the input buffers."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = _build("dspg", problem)
    exec_chunk = runner._make_resident_exec(algo, "host", kernel="pallas")

    L, m, d = 4, 4, 12
    state = jax.tree.map(lambda a: jnp.array(a, copy=True), algo.init())
    carry = (state, jnp.zeros((), jnp.int32))
    # run-level xs of two chunks; this chunk reads the first L rows
    batch = {"features": np.zeros((2 * L, m, 1, d), np.float32),
             "labels": np.zeros((2 * L, m, 1), np.float32)}
    xs, shapes = runner._stage_rows(
        (batch, np.stack([np.eye(m)] * 2 * L),
         np.ones(2 * L, np.float32), np.ones(2 * L, bool)), (False,) * 4)
    compiled = exec_chunk.lower(carry, xs, data, L, shapes).compile()
    assert "input_output_alias" in compiled.as_text()

    out, offset = exec_chunk(carry, xs, data, L, shapes)
    assert state.params.is_deleted()          # donated, not copied
    assert not out.params.is_deleted()
    assert int(offset) == L                   # the next chunk's first row


def test_resident_kernel_transfer_ledger_is_o1():
    """The fused path changes the chunk body only — staging, dispatch and
    history pull are untouched, so the O(1) transfer ledger must hold
    under the XLA transfer guard exactly as for the unfused executor."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    old = runner._RESIDENT_DISPATCH_GUARD
    runner._RESIDENT_DISPATCH_GUARD = lambda: jax.transfer_guard("disallow")
    try:
        res = runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(resident=True, kernel="pallas", gossip="dense"), seed=0,
                         record_every=5)
    finally:
        runner._RESIDENT_DISPATCH_GUARD = old
    assert res.extras["transfers_h2d"] == 1
    assert res.extras["transfers_d2h"] <= 2
    assert res.history.objective[-1] < res.history.objective[0]


def test_resident_kernel_knob_validation():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _sched()
    with pytest.raises(ValueError, match="kernel"):
        runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(kernel="bogus"))
    with pytest.raises(ValueError, match="resident"):
        runner.run(_build("dspg", problem), problem, sched, exec=ExecSpec(kernel="pallas"))
