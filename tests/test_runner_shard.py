"""Sharded execution (``ExecSpec(shard=...)``) on a forced 4-device
host-platform CPU mesh, in subprocesses (the main test process keeps its
single real device — see ``run_multi_device`` in conftest).

Covers the PR's two GSPMD partitionings:

* ``shard="cells"`` — a batched sweep's CELL axis split over a ``cells``
  mesh: histories equal the unsharded batched program to float tolerance
  for every registered algorithm, with the O(1) transfer ledger intact.
* ``shard="nodes"`` — a single resident run's stacked ``(m, d)`` node axis
  split over the mesh the transport rides: dense and ppermute histories
  equal the unsharded run, ``compressed(ppermute)`` quantizes the local
  shard BEFORE the collective (wire accounting exact at bits/32 with the
  per-link map summing to ``bytes_per_step``).

Host-side validation errors (divisibility, cells+ppermute conflicts) run
in-process — they fire before any device work.
"""

import textwrap

import pytest

from repro.core import algorithm, graphs, prox, runner, sweep
from repro.core.exec_spec import ExecSpec

_PRELUDE = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner, \\
        sweep, transport
    from repro.core.exec_spec import ExecSpec
    from repro.core.mesh import make_mesh
    from repro.data import synthetic

    def loss(w, batch):
        logits = batch["features"] @ w
        return jnp.mean(-batch["labels"] * logits
                        + jnp.log1p(jnp.exp(logits)))

    def make_problem(m, d=10, n=96):
        ds = synthetic.make_classification(n=n, d=d, seed=0)
        data = {k: jnp.asarray(v)
                for k, v in synthetic.partition_per_node(ds, m).items()}
        return algorithm.Problem(loss, prox.l1(0.01),
                                 gossip.stack_tree(jnp.zeros(d), m), data)

    def hist_err(a, b):
        return float(np.max(np.abs(np.asarray(a.history.objective)
                                   - np.asarray(b.history.objective))))

    FACTORIES = {
        "dpsvrg": lambda p: algorithm.dpsvrg_algorithm(
            p, dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3,
                                        num_outer=3, k_max=2)),
        "dspg": lambda p: algorithm.dspg_algorithm(
            p, dpsvrg.DSPGHyperParams(alpha0=0.3), 18),
        "dpg": lambda p: algorithm.dpg_algorithm(p, 0.3, 18),
        "gt_svrg": lambda p: algorithm.gt_svrg_algorithm(p, 0.1, 3, 6),
        "loopless_dpsvrg": lambda p: algorithm.loopless_dpsvrg_algorithm(
            p, 0.3, 18, snapshot_prob=0.1),
        "dvr": lambda p: algorithm.dvr_algorithm(
            p, 0.3, 18, rho=0.7, snapshot_prob=0.1),
        "inexact_prox_svrg": lambda p: algorithm.ALGORITHMS[
            "inexact_prox_svrg"](p, __import__(
                "repro.core.inexact", fromlist=["InexactHyperParams"]
            ).InexactHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3)),
    }
""")


_CELLS_SCRIPT = _PRELUDE + textwrap.dedent("""
    out = {"devices": len(jax.devices()), "errs": {}, "ledgers": {}}
    sched = graphs.b_connected_ring_schedule(4, b=1, seed=0)
    for name, factory in FACTORIES.items():
        m = 1 if name == "inexact_prox_svrg" else 4
        problem = make_problem(m)
        cell_sched = (graphs.static_schedule(np.eye(1), name="centralized")
                      if m == 1 else sched)

        def build(_f=factory, _p=problem):
            return _f(_p), _p

        grid = {"seed": [0, 1, 2, 3]}
        plain = sweep.run_sweep(build, grid, cell_sched,
                                ExecSpec(resident=True, gossip="dense"),
                                record_every=4)
        sharded = sweep.run_sweep(
            build, grid, cell_sched,
            ExecSpec(resident=True, gossip="dense", shard="cells"),
            record_every=4)
        out["errs"][name] = hist_err(plain, sharded)
        out["ledgers"][name] = [sharded.extras["transfers_h2d"],
                                sharded.extras["transfers_d2h"]]
    print(json.dumps(out))
""")


def test_sharded_cells_matches_unsharded_all_algorithms(run_multi_device):
    out = run_multi_device(_CELLS_SCRIPT, devices=4)
    assert out["devices"] == 4
    assert set(out["errs"]) == set(algorithm.ALGORITHMS)
    for name, err in out["errs"].items():
        assert err < 1e-5, (name, err)
    for name, (h2d, d2h) in out["ledgers"].items():
        assert h2d <= 2 and d2h <= 2, (name, h2d, d2h)


_CELLS_TOPOLOGY_SCRIPT = _PRELUDE + textwrap.dedent("""
    out = {}
    problem = make_problem(4)
    scheds = [graphs.b_connected_ring_schedule(4, b=b, seed=b)
              for b in (1, 2, 1, 3)]

    def build(_p=problem):
        return FACTORIES["loopless_dpsvrg"](_p), _p

    grid = {"schedule": scheds, "seed": [0, 1, 2, 3]}
    plain = sweep.run_sweep(build, grid,
                            exec=ExecSpec(resident=True, gossip="dense"),
                            record_every=4, mode="zip")
    mesh = make_mesh((4,), ("cells",))
    sharded = sweep.run_sweep(
        build, grid,
        exec=ExecSpec(resident=True, gossip="dense", mesh=mesh,
                      shard="cells"),
        record_every=4, mode="zip")
    out["err"] = hist_err(plain, sharded)
    out["wire_equal"] = bool(
        (np.asarray(plain.extras["wire_bytes"])
         == np.asarray(sharded.extras["wire_bytes"])).all())
    out["h2d"] = sharded.extras["transfers_h2d"]
    print(json.dumps(out))
""")


def test_sharded_cells_topology_grid_with_explicit_mesh(run_multi_device):
    out = run_multi_device(_CELLS_TOPOLOGY_SCRIPT, devices=4)
    assert out["err"] < 1e-5, out
    assert out["wire_equal"], out
    assert out["h2d"] <= 2, out


_NODES_SCRIPT = _PRELUDE + textwrap.dedent("""
    out = {"devices": len(jax.devices())}
    m = 4
    problem = make_problem(m)
    ring = graphs.b_connected_ring_schedule(m, b=1, seed=0)

    # dense gossip, node axis sharded over a fresh all-device mesh
    plain = runner.run(FACTORIES["loopless_dpsvrg"](problem), problem, ring,
                       ExecSpec(resident=True, gossip="dense"),
                       seed=0, record_every=4)
    sharded = runner.run(FACTORIES["loopless_dpsvrg"](problem), problem,
                         ring,
                         ExecSpec(resident=True, gossip="dense",
                                  shard="nodes"),
                         seed=0, record_every=4)
    out["dense_err"] = hist_err(plain, sharded)
    out["dense_ledger"] = [sharded.extras["transfers_h2d"],
                           sharded.extras["transfers_d2h"]]
    out["wire_equal"] = bool(
        (np.asarray(plain.extras["wire_bytes"])
         == np.asarray(sharded.extras["wire_bytes"])).all())

    # ppermute: the transport's own mesh doubles as the shard mesh
    pperm = runner.run(FACTORIES["dspg"](problem), problem, ring,
                       ExecSpec(resident=True, gossip="ppermute",
                                shard="nodes"),
                       seed=1, record_every=6)
    ref = runner.run(FACTORIES["dspg"](problem), problem, ring,
                     ExecSpec(resident=True, gossip="dense"),
                     seed=1, record_every=6)
    out["pperm_err"] = hist_err(ref, pperm)

    # compressed(ppermute): quantize-before-collective — histories match
    # the single-device compressed(dense) run, wire charged at bits/32 with
    # the per-link map summing exactly to bytes_per_step
    bits = 4
    cp = transport.CompressedBackend(inner="ppermute", bits=bits)
    cd = transport.CompressedBackend(inner="dense", bits=bits)
    algo = FACTORIES["loopless_dpsvrg"]
    rp = runner.run(algo(problem), problem, ring,
                    ExecSpec(resident=True, gossip=cp, shard="nodes"),
                    seed=2, record_every=4)
    rd = runner.run(algo(problem), problem, ring,
                    ExecSpec(resident=True, gossip=cd),
                    seed=2, record_every=4)
    out["compressed_err"] = hist_err(rd, rp)
    out["wire_ratio32"] = int(
        np.asarray(rd.extras["wire_bytes"])[-1] * bits
        // np.asarray(rp.extras["wire_bytes"])[-1])

    # exact per-link accounting for bits in {4, 3} (3 exercises the
    # rounding-remainder distribution)
    pc = transport.node_param_count(problem.x0)
    meta = algo(problem).meta
    exact = {}
    for b in (4, 3):
        be = transport.CompressedBackend(inner="ppermute", bits=b)
        aux = be.prepare(ring, meta, mesh=None)
        ok = True
        for slot in range(meta.slot_start, meta.slot_start + 3):
            phi = be.phi_for(aux, slot, 2)
            links = be.bytes_per_link(aux, phi, pc)
            ok = ok and (sum(links.values())
                         == be.bytes_per_step(aux, phi, pc))
        exact[str(b)] = bool(ok)
    out["link_sums_exact"] = exact
    print(json.dumps(out))
""")


def test_sharded_nodes_matches_unsharded(run_multi_device):
    out = run_multi_device(_NODES_SCRIPT, devices=4)
    assert out["devices"] == 4
    assert out["dense_err"] < 1e-5, out
    h2d, d2h = out["dense_ledger"]
    assert h2d <= 2 and d2h <= 2, out
    assert out["wire_equal"], out
    assert out["pperm_err"] < 1e-5, out
    assert out["compressed_err"] < 1e-4, out
    # rd charges bits/32 of f32; rp must charge the same -> ratio*bits == bits
    assert out["wire_ratio32"] == 4, out
    assert out["link_sums_exact"] == {"4": True, "3": True}, out


_NODES_STAGING_SCRIPT = _PRELUDE + textwrap.dedent("""
    out = {"devices": len(jax.devices()), "cases": {}}
    m = 4
    problem = make_problem(m)
    ring = graphs.b_connected_ring_schedule(m, b=1, seed=0)

    def job(num_outer):
        return algorithm.dpsvrg_algorithm(problem, dpsvrg.DPSVRGHyperParams(
            alpha=0.3, beta=1.2, n0=3, num_outer=num_outer, batch_size=2))

    for sampling in ("host", "device"):
        for transitions in (True, False):
            kw = dict(resident=True, gossip="dense", sampling=sampling,
                      device_transitions=transitions)
            # record_every 3 cuts DPSVRG's rounds into chunks of three
            # bucket lengths (3, 1, 2)
            plain = runner.run(job(4), problem, ring, ExecSpec(**kw),
                               seed=3, record_every=3)
            sharded = runner.run(job(4), problem, ring,
                                 ExecSpec(shard="nodes", **kw),
                                 seed=3, record_every=3)
            longer = runner.run(job(8), problem, ring,
                                ExecSpec(shard="nodes", **kw),
                                seed=3, record_every=3)
            out["cases"][f"{sampling}-{transitions}"] = {
                "err": hist_err(plain, sharded),
                "params_err": float(np.max(np.abs(
                    np.asarray(plain.params) - np.asarray(sharded.params)))),
                "buffers": [plain.extras["staged_buffers"],
                            sharded.extras["staged_buffers"],
                            longer.extras["staged_buffers"]],
                "h2d": sharded.extras["transfers_h2d"]}
    print(json.dumps(out))
""")


def test_sharded_nodes_stages_one_buffer_per_leaf(run_multi_device):
    """``shard="nodes"`` stages the run-level xs with one sharding per
    leaf: as many buffers as the unsharded run, not growing with the
    chunk count, and the same history and params to float tolerance, for
    both sampling modes with transitions folded in or dispatched."""
    out = run_multi_device(_NODES_STAGING_SCRIPT, devices=4)
    assert out["devices"] == 4
    for name, case in out["cases"].items():
        assert case["err"] < 1e-5 and case["params_err"] < 1e-5, (name, case)
        # batch features and labels (host sampling), phi, alpha, keep, and
        # the four transition flags
        want = ((2 if name.startswith("host") else 0) + 3
                + (4 if name.endswith("True") else 0))
        assert case["buffers"] == [want] * 3, (name, case)
        assert case["h2d"] == 1, (name, case)


# ---------------------------------------------------------------------------
# host-side validation (fires before any device work)
# ---------------------------------------------------------------------------

def _tiny_problem(m=3, d=6):
    import jax.numpy as jnp

    from repro.core import gossip
    from repro.data import synthetic

    def loss(w, batch):
        logits = batch["features"] @ w
        return jnp.mean(-batch["labels"] * logits
                        + jnp.log1p(jnp.exp(logits)))

    ds = synthetic.make_classification(n=48, d=d, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in synthetic.partition_per_node(ds, m).items()}
    return algorithm.Problem(loss, prox.l1(0.01),
                             gossip.stack_tree(jnp.zeros(d), m), data)


def test_shard_cells_on_run_points_at_run_sweep():
    problem = _tiny_problem()
    sched = graphs.b_connected_ring_schedule(3, b=1, seed=0)
    algo = algorithm.loopless_dpsvrg_algorithm(problem, 0.3, 6,
                                               snapshot_prob=0.1)
    with pytest.raises(ValueError, match="run_sweep"):
        runner.run(algo, problem, sched,
                   ExecSpec(resident=True, shard="cells"))


def test_shard_nodes_on_sweep_points_at_run():
    problem = _tiny_problem()
    sched = graphs.b_connected_ring_schedule(3, b=1, seed=0)

    def build():
        return algorithm.loopless_dpsvrg_algorithm(problem, 0.3, 6,
                                                   snapshot_prob=0.1), problem

    with pytest.raises(ValueError, match="runner.run"):
        sweep.run_sweep(build, {"seed": [0, 1]}, sched,
                        ExecSpec(resident=True, shard="nodes"))


def test_shard_cells_rejects_mesh_collective_transport():
    problem = _tiny_problem()
    sched = graphs.b_connected_ring_schedule(3, b=1, seed=0)

    def build():
        return algorithm.dspg_algorithm(
            problem, __import__("repro.core.dpsvrg",
                                fromlist=["DSPGHyperParams"])
            .DSPGHyperParams(alpha0=0.3), 6), problem

    with pytest.raises(ValueError, match="shard='nodes'"):
        sweep.run_sweep(build, {"seed": [0]}, sched,
                        ExecSpec(resident=True, gossip="ppermute",
                                 shard="cells"))


def test_shard_cells_grid_must_divide_device_count():
    problem = _tiny_problem()
    sched = graphs.b_connected_ring_schedule(3, b=1, seed=0)

    def build():
        return algorithm.loopless_dpsvrg_algorithm(problem, 0.3, 6,
                                                   snapshot_prob=0.1), problem

    import jax
    ndev = len(jax.devices())
    # a grid size coprime with any device count >= 2; on the single-device
    # main process every size divides, so force the mismatch via a mesh
    # check against the fresh all-device mesh
    if ndev == 1:
        pytest.skip("single device: every grid size divides")
    with pytest.raises(ValueError, match="split evenly"):
        sweep.run_sweep(build, {"seed": list(range(ndev + 1))}, sched,
                        ExecSpec(resident=True, gossip="dense",
                                 shard="cells"))


def test_shard_nodes_divisibility_error_is_helpful(run_multi_device):
    script = _PRELUDE + textwrap.dedent("""
        problem = make_problem(3)
        ring = graphs.b_connected_ring_schedule(3, b=1, seed=0)
        out = {}
        try:
            runner.run(FACTORIES["loopless_dpsvrg"](problem), problem, ring,
                       ExecSpec(resident=True, gossip="dense",
                                shard="nodes"))
            out["raised"] = False
        except ValueError as e:
            out["raised"] = True
            out["msg_has_divide"] = "divis" in str(e)

        def build():
            return FACTORIES["loopless_dpsvrg"](problem), problem

        try:
            sweep.run_sweep(build, {"seed": [0, 1, 2]}, ring,
                            ExecSpec(resident=True, gossip="dense",
                                     shard="cells"))
            out["cells_raised"] = False
        except ValueError as e:
            out["cells_raised"] = True
            out["cells_msg"] = "split evenly" in str(e)
        print(json.dumps(out))
    """)
    out = run_multi_device(script, devices=4)
    assert out["raised"] and out["msg_has_divide"], out
    assert out["cells_raised"] and out["cells_msg"], out
