"""Unified Algorithm/runner API: bit-for-bit equivalence with the frozen
pre-refactor loops (tests/_legacy_runs.py), scan-vs-host agreement, the
double-final-record fix, and the pluggable recorder/registry surface.

All comparisons drive ``algorithm.ALGORITHMS`` factories through
``runner.run`` directly — the deprecated ``*_run`` wrappers are gone."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner
from repro.data import synthetic
from tests import _legacy_runs as legacy, conftest


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
    sched = graphs.b_connected_ring_schedule(m, b=2, seed=0)
    x0 = gossip.stack_tree(jnp.zeros(d), m)
    return data, h, sched, x0


def _run(name, data, h, x0, sched, *factory_args, **kw):
    """runner.run with the historical (params, history) return shape."""
    res = conftest.run_named_algorithm(logreg_loss, name, data, h, x0, sched,
                                       *factory_args, **kw)
    return res.params, res.history


def _assert_hist_equal(a, b):
    for field in runner.RunHistory._fields:
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field),
                                      err_msg=field)


def _assert_params_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Seed-identical histories vs the pre-refactor loops
# ---------------------------------------------------------------------------

def test_dpsvrg_matches_legacy_inner_records():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4)
    # K_s = (4, 5, 6, 7): the last inner step is NOT on the record cadence,
    # so legacy emits no duplicate and the histories must match exactly.
    pl_, hl = legacy.legacy_dpsvrg_run(logreg_loss, h, x0, data, sched, hp,
                                       seed=1, record_every=3)
    pn, hn = _run("dpsvrg", data, h, x0, sched, hp, seed=1, record_every=3)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


def test_dpsvrg_matches_legacy_per_round():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4,
                                  k_max=3)
    pl_, hl = legacy.legacy_dpsvrg_run(logreg_loss, h, x0, data, sched, hp,
                                       seed=7, record_every=0)
    pn, hn = _run("dpsvrg", data, h, x0, sched, hp, seed=7, record_every=0)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


def test_dpsvrg_final_record_deduplicated():
    """The documented fix: when the last inner step lands exactly on the
    record cadence, legacy appended the terminal point twice; the unified
    runner emits it once (history = legacy without the duplicate row)."""
    data, h, sched, x0 = _setup()
    # single outer round, K_1 = ceil(1.2 * 2) = 3 = record_every
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=2, num_outer=1)
    _, hl = legacy.legacy_dpsvrg_run(logreg_loss, h, x0, data, sched, hp,
                                     seed=1, record_every=3)
    _, hn = _run("dpsvrg", data, h, x0, sched, hp, seed=1, record_every=3)
    assert hl.objective[-1] == hl.objective[-2]          # legacy duplicate
    assert hl.steps[-1] == hl.steps[-2]
    dedup = runner.RunHistory(*(col[:-1] for col in hl))
    _assert_hist_equal(dedup, hn)


def test_dspg_matches_legacy():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    pl_, hl = legacy.legacy_dspg_run(logreg_loss, h, x0, data, sched, hp,
                                     num_steps=40, seed=2, record_every=7)
    pn, hn = _run("dspg", data, h, x0, sched, hp, 40, seed=2, record_every=7)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


def test_dpg_matches_legacy():
    data, h, sched, x0 = _setup()
    pl_, hl = legacy.legacy_dpg_run(logreg_loss, h, x0, data, sched,
                                    alpha=0.3, num_steps=25, record_every=4)
    pn, hn = _run("dpg", data, h, x0, sched, 0.3, 25, record_every=4)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


@pytest.mark.parametrize("record_every", [0, 5])
def test_gt_svrg_matches_legacy(record_every):
    data, h, sched, x0 = _setup()
    pl_, hl = legacy.legacy_gt_svrg_run(logreg_loss, h, x0, data, sched,
                                        alpha=0.2, num_outer=3, inner_steps=7,
                                        seed=3, record_every=record_every)
    pn, hn = _run("gt_svrg", data, h, x0, sched, 0.2, 3, 7, seed=3,
                  record_every=record_every)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


def test_loopless_matches_legacy():
    data, h, sched, x0 = _setup()
    pl_, hl = legacy.legacy_loopless_dpsvrg_run(
        logreg_loss, h, x0, data, sched, alpha=0.3, num_steps=30,
        snapshot_prob=0.15, seed=4, record_every=6)
    pn, hn = _run("loopless_dpsvrg", data, h, x0, sched, 0.3, 30,
                  snapshot_prob=0.15, seed=4, record_every=6)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


def test_compressed_dpsvrg_matches_legacy():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3,
                                  compress_bits=8)
    pl_, hl = legacy.legacy_dpsvrg_run(logreg_loss, h, x0, data, sched, hp,
                                       seed=5, record_every=0)
    pn, hn = _run("dpsvrg", data, h, x0, sched, hp, seed=5, record_every=0)
    _assert_hist_equal(hl, hn)
    _assert_params_equal(pl_, pn)


# ---------------------------------------------------------------------------
# lax.scan fast path agrees with the host loop
# ---------------------------------------------------------------------------

def _assert_scan_agrees(a, b):
    for field in ("epochs", "comm_rounds", "steps"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field),
                                      err_msg=field)
    np.testing.assert_allclose(a.objective, b.objective, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(a.consensus, b.consensus, rtol=1e-4, atol=1e-6)


def test_scan_path_matches_host_dpsvrg():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4)
    _, host = _run("dpsvrg", data, h, x0, sched, hp, seed=1, record_every=3)
    _, scan = _run("dpsvrg", data, h, x0, sched, hp, seed=1, record_every=3,
                   scan=True)
    _assert_scan_agrees(host, scan)


def test_scan_path_matches_host_dspg():
    data, h, sched, x0 = _setup()
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    _, host = _run("dspg", data, h, x0, sched, hp, 40, seed=2, record_every=8)
    _, scan = _run("dspg", data, h, x0, sched, hp, 40, seed=2, record_every=8,
                   scan=True)
    _assert_scan_agrees(host, scan)


def test_scan_path_matches_host_loopless_coin_flips():
    """Coin-flip snapshot refreshes cut scan chunks mid-interval; the rng
    draw order (batch, coin, batch, ...) must still match the host loop."""
    data, h, sched, x0 = _setup()
    _, host = _run("loopless_dpsvrg", data, h, x0, sched, 0.3, 30,
                   snapshot_prob=0.2, seed=4, record_every=6)
    _, scan = _run("loopless_dpsvrg", data, h, x0, sched, 0.3, 30,
                   snapshot_prob=0.2, seed=4, record_every=6, scan=True)
    _assert_scan_agrees(host, scan)


# ---------------------------------------------------------------------------
# Protocol surface: registry, metadata, pluggable recorders
# ---------------------------------------------------------------------------

def test_registry_covers_all_algorithms():
    assert set(algorithm.ALGORITHMS) >= {
        "dpsvrg", "dspg", "dpg", "gt_svrg", "loopless_dpsvrg",
        "inexact_prox_svrg"}
    data, h, sched, x0 = _setup()
    problem = algorithm.Problem(logreg_loss, h, x0, data)
    algo = algorithm.ALGORITHMS["dspg"](
        problem, dpsvrg.DSPGHyperParams(alpha0=0.2), 10)
    assert algo.meta.name == "dspg"
    assert algo.meta.num_steps == 10


def test_meta_declares_cost_and_gossip_policy():
    data, h, sched, x0 = _setup()
    problem = algorithm.Problem(logreg_loss, h, x0, data)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=2,
                                  k_max=2)
    meta = algorithm.dpsvrg_algorithm(problem, hp).meta
    assert meta.step_grad_factor == 2            # SVRG: two grads per sample
    assert meta.outer_full_grad                  # m*n per snapshot refresh
    assert [meta.gossip_rounds(k) for k in (1, 2, 3, 4)] == [1, 2, 2, 2]
    assert algorithm.dpg_algorithm(problem, 0.1, 5).meta.epoch_metric == "steps"


def test_extra_metric_recorders():
    data, h, sched, x0 = _setup()
    problem = algorithm.Problem(logreg_loss, h, x0, data)
    algo = algorithm.dspg_algorithm(problem, dpsvrg.DSPGHyperParams(alpha0=0.3),
                                    num_steps=12)
    res = runner.run(algo, problem, sched, seed=0, record_every=4,
                     extra_metrics={
                         "max_abs": lambda p: float(jnp.max(jnp.abs(p))),
                         "nnz": lambda p: float(jnp.sum(jnp.abs(p) > 0)),
                     })
    # wire_bytes is the always-present driver-supplied column (transport
    # backend byte accounting); user recorders ride alongside it, plus the
    # scalar transfer-ledger entries the resident path is gated on (events
    # and their bytes)
    assert set(res.extras) == {"max_abs", "nnz", "wire_bytes",
                               "transfers_h2d", "transfers_d2h",
                               "bytes_h2d", "bytes_d2h"}
    for name in ("max_abs", "nnz", "wire_bytes"):
        assert res.extras[name].shape == res.history.objective.shape
    assert res.extras["max_abs"][-1] > 0.0
    assert res.extras["wire_bytes"][-1] > 0
    assert res.extras["transfers_h2d"] > 0


def test_run_result_shapes():
    data, h, sched, x0 = _setup()
    problem = algorithm.Problem(logreg_loss, h, x0, data)
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    res = runner.run(algorithm.dspg_algorithm(problem, hp, 15), problem,
                     sched, seed=9, record_every=5)
    assert np.asarray(res.params).shape == np.asarray(x0).shape
    # initial record + every 5 steps
    np.testing.assert_array_equal(res.history.steps, [0, 5, 10, 15])
