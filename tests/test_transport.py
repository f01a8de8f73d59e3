"""GossipBackend API coverage: registry + "auto" selection, the
``gossip_mode`` deprecation shim, wire-byte accounting, the ``compressed``
transport (error-feedback over any inner wire format), and dense-vs-ppermute
history equivalence on a forced 4-device host-platform CPU mesh."""

import functools
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (algorithm, compression, dpsvrg, gossip, graphs, prox,
                        runner, transport)
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


def _ring(m):
    return graphs.b_connected_ring_schedule(m, b=1, seed=0)


def _assert_agrees(a, b):
    for field in ("epochs", "comm_rounds", "steps"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field),
                                      err_msg=field)
    np.testing.assert_allclose(a.objective, b.objective, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(a.consensus, b.consensus, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# registry + "auto" selection
# ---------------------------------------------------------------------------

def test_registry_has_all_four_backends():
    assert set(transport.GOSSIP_BACKENDS) == {
        "dense", "banded", "ppermute", "compressed"}
    for name, backend in transport.GOSSIP_BACKENDS.items():
        assert backend.name == name


def test_auto_selection_rule():
    """Faithful multi-consensus (unbounded k) saturates the band-offset
    union -> dense; k_max-capped DPSVRG on a ring keeps O(degree) band
    structure -> banded."""
    data, h, x0 = _setup(m=8)
    problem = _problem(data, h, x0)
    sched = _ring(8)
    faithful = algorithm.dpsvrg_algorithm(
        problem, dpsvrg.DPSVRGHyperParams(alpha=0.2, beta=1.2, n0=4,
                                          num_outer=6)).meta
    capped = algorithm.dpsvrg_algorithm(
        problem, dpsvrg.DPSVRGHyperParams(alpha=0.2, beta=1.2, n0=4,
                                          num_outer=6, k_max=2)).meta
    assert transport.select_backend_name(sched, faithful) == "dense"
    assert transport.select_backend_name(sched, capped) == "banded"


def test_auto_dense_fallback_replaces_saturation_warning():
    """Faithful multi-consensus under gossip="auto" runs on the dense
    backend with NO RuntimeWarning (the old band-saturation warning path),
    bit-for-bit identical to an explicit gossip="dense" run."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _ring(4)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3)
    runs = {}
    for mode in ("auto", "dense"):
        algo = algorithm.dpsvrg_algorithm(problem, hp)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            runs[mode] = runner.run(algo, problem, sched, exec=ExecSpec(gossip=mode), seed=3,
                                    record_every=0).history
    for field in runner.RunHistory._fields:
        np.testing.assert_array_equal(getattr(runs["auto"], field),
                                      getattr(runs["dense"], field))


def test_auto_selects_banded_and_matches_dense():
    data, h, x0 = _setup(m=6)
    problem = _problem(data, h, x0)
    sched = _ring(6)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4,
                                  k_max=2)
    runs = {}
    for mode in ("auto", "dense"):
        algo = algorithm.dpsvrg_algorithm(problem, hp)
        runs[mode] = runner.run(algo, problem, sched, exec=ExecSpec(scan=True, gossip=mode), seed=1, record_every=3)
    _assert_agrees(runs["auto"].history, runs["dense"].history)
    # auto picked the banded wire format: strictly fewer bytes than dense
    assert (runs["auto"].extras["wire_bytes"][-1]
            < runs["dense"].extras["wire_bytes"][-1])


def test_unknown_backend_raises():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = algorithm.dspg_algorithm(
        problem, dpsvrg.DSPGHyperParams(alpha0=0.3), num_steps=4)
    with pytest.raises(ValueError, match="unknown gossip backend"):
        runner.run(algo, problem, _ring(4), exec=ExecSpec(gossip="sparse"))


def test_backend_instance_is_accepted():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _ring(4)
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    runs = {}
    for g in ("banded", transport.BandedBackend()):
        algo = algorithm.dspg_algorithm(problem, hp, num_steps=12)
        runs[str(g)] = runner.run(algo, problem, sched, exec=ExecSpec(gossip=g), seed=2,
                                  record_every=4).history
    a, b = runs.values()
    np.testing.assert_array_equal(a.objective, b.objective)


# ---------------------------------------------------------------------------
# gossip_mode deprecation shim
# ---------------------------------------------------------------------------

def test_gossip_mode_shim_warns_and_maps():
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = graphs.MixingSchedule(
        tuple(graphs.edge_matching_matrices(4)), b=2, eta=0.5,
        name="matching4")
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    algo = algorithm.dspg_algorithm(problem, hp, num_steps=12)
    with pytest.warns(DeprecationWarning, match="gossip_mode"):
        old = runner.run(algo, problem, sched, seed=2, record_every=4,
                         gossip_mode="banded").history
    algo = algorithm.dspg_algorithm(problem, hp, num_steps=12)
    new = runner.run(algo, problem, sched, exec=ExecSpec(gossip="banded"), seed=2, record_every=4).history
    for field in runner.RunHistory._fields:
        np.testing.assert_array_equal(getattr(old, field),
                                      getattr(new, field))


# ---------------------------------------------------------------------------
# wire-byte accounting
# ---------------------------------------------------------------------------

def test_wire_bytes_column_banded_below_dense():
    data, h, x0 = _setup(m=8, d=12)
    problem = _problem(data, h, x0)
    sched = _ring(8)
    hp = dpsvrg.DSPGHyperParams(alpha0=0.3)
    res = {}
    for mode in ("dense", "banded"):
        algo = algorithm.dspg_algorithm(problem, hp, num_steps=20)
        res[mode] = runner.run(algo, problem, sched, exec=ExecSpec(gossip=mode), seed=0, record_every=5)
    for mode, r in res.items():
        wb = r.extras["wire_bytes"]
        assert wb.shape == r.history.objective.shape
        assert wb[0] == 0 and np.all(np.diff(wb) > 0), mode
    # dense all-gathers all m copies: m*(m-1)*d*4 per step; the ring's
    # banded form moves 2 point-to-point bands: 2*m*d*4 per step
    m, d = 8, 12
    assert res["dense"].extras["wire_bytes"][-1] == 20 * m * (m - 1) * d * 4
    assert res["banded"].extras["wire_bytes"][-1] == 20 * 2 * m * d * 4


def test_compressed_wire_bytes_are_quarter_of_inner():
    data, h, x0 = _setup(m=8)
    problem = _problem(data, h, x0)
    sched = _ring(8)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3,
                                  k_max=2)
    res = {}
    for g in ("dense", transport.CompressedBackend(inner="dense", bits=8)):
        algo = algorithm.dpsvrg_algorithm(problem, hp)
        res[str(g)] = runner.run(algo, problem, sched, exec=ExecSpec(gossip=g), seed=0, record_every=0)
    dense_wb, comp_wb = (r.extras["wire_bytes"][-1] for r in res.values())
    assert comp_wb == dense_wb // 4          # int8 over f32 wire


# ---------------------------------------------------------------------------
# compressed transport: error feedback over any inner wire format
# ---------------------------------------------------------------------------

def test_compressed_backend_equals_legacy_hp_compression():
    """gossip="compressed" on a plain DPSVRG build is the SAME computation
    as the legacy hp.compress_bits build on the dense transport —
    bit-for-bit, since both route through CompressedPhi/mix_with_state."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _ring(4)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3)
    hp_legacy = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3,
                                         num_outer=3, compress_bits=8)
    new = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem, sched, exec=ExecSpec(gossip="compressed"),
                     seed=5, record_every=0)
    old = runner.run(algorithm.dpsvrg_algorithm(problem, hp_legacy), problem,
                     sched, exec=ExecSpec(gossip="dense"), seed=5, record_every=0)
    for field in runner.RunHistory._fields:
        np.testing.assert_array_equal(getattr(new.history, field),
                                      getattr(old.history, field))
    np.testing.assert_array_equal(np.asarray(new.params),
                                  np.asarray(old.params))
    # the hp-level run's wire accounting reflects the int8 payload too (the
    # runner wraps the resolved transport at meta.compress_bits)
    np.testing.assert_array_equal(old.extras["wire_bytes"],
                                  new.extras["wire_bytes"])


def test_conflicting_compression_bits_raise():
    """hp-level quantization at one width + a compressed transport at
    another is a config contradiction — loud error, not a silent pick."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=2,
                                  compress_bits=4)
    algo = algorithm.dpsvrg_algorithm(problem, hp)
    with pytest.raises(ValueError, match="conflicting compression"):
        runner.run(algo, problem, _ring(4), exec=ExecSpec(gossip=transport.CompressedBackend(bits=8)))
    # agreeing widths are fine
    res = runner.run(algo, problem, _ring(4), exec=ExecSpec(gossip=transport.CompressedBackend(bits=4)), record_every=0)
    assert res.history.objective.shape[0] > 0


def test_explicit_banded_on_saturated_schedule_warns():
    """auto silently falls back to dense, but explicitly requesting banded
    on a saturated band union keeps the diagnostic."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _ring(4)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=3)
    algo = algorithm.dpsvrg_algorithm(problem, hp)
    with pytest.warns(RuntimeWarning, match="band offsets"):
        runner.run(algo, problem, sched, exec=ExecSpec(gossip="banded"), seed=3, record_every=0)


def test_compressed_error_feedback_converges_on_paper_logreg():
    """Satellite smoke test: error-feedback compressed gossip on the paper
    logreg problem tracks the uncompressed run at 4x fewer wire bytes."""
    m = 8
    ds = synthetic.make_paper_dataset("adult_like", scale=0.02, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in synthetic.partition_per_node(ds, m).items()}
    h = prox.l1(0.01)
    x0 = gossip.stack_tree(jnp.zeros(ds.dim), m)
    problem = _problem(data, h, x0)
    sched = _ring(m)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=4, num_outer=10,
                                  k_max=2)
    full = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                      sched, exec=ExecSpec(scan=True, gossip="dense"), seed=0, record_every=0)
    comp = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                      sched, exec=ExecSpec(scan=True, gossip="compressed"), seed=0, record_every=0)
    assert comp.history.objective[-1] < comp.history.objective[0] - 0.03
    assert abs(comp.history.objective[-1] - full.history.objective[-1]) < 5e-3
    assert (comp.extras["wire_bytes"][-1]
            == full.extras["wire_bytes"][-1] // 4)


def test_compressed_wraps_banded_inner():
    """The compressed payload rides the banded wire format: CompressedPhi
    composes with BandedPhi (scan path included) and stays close to the
    dense-inner compressed run."""
    data, h, x0 = _setup(m=6)
    problem = _problem(data, h, x0)
    sched = _ring(6)
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4,
                                  k_max=2)
    runs = {}
    for inner in ("dense", "banded"):
        algo = algorithm.dpsvrg_algorithm(problem, hp)
        runs[inner] = runner.run(
            algo, problem, sched, exec=ExecSpec(scan=True, gossip=transport.CompressedBackend(inner=inner, bits=8)), seed=1, record_every=3)
    _assert_agrees(runs["dense"].history, runs["banded"].history)
    assert (runs["banded"].extras["wire_bytes"][-1]
            < runs["dense"].extras["wire_bytes"][-1])


def test_compressed_rejects_stateless_algorithm():
    """Algorithms that don't thread a mix state can't ride the stateful
    compressed transport — clear error, not silent wrong numbers."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = algorithm.dspg_algorithm(
        problem, dpsvrg.DSPGHyperParams(alpha0=0.3), num_steps=4)
    with pytest.raises(ValueError, match="mix state"):
        runner.run(algo, problem, _ring(4), exec=ExecSpec(gossip="compressed"))


# ---------------------------------------------------------------------------
# ppermute transport (forced 4-device host-platform CPU mesh, subprocess)
# ---------------------------------------------------------------------------

_PPERMUTE_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner, \\
        transport
    from repro.core.exec_spec import ExecSpec
    from repro.core.mesh import make_mesh
    from repro.data import synthetic

    def loss(w, batch):
        logits = batch["features"] @ w
        return jnp.mean(-batch["labels"] * logits
                        + jnp.log1p(jnp.exp(logits)))

    m = 4
    ds = synthetic.make_classification(n=96, d=10, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in synthetic.partition_per_node(ds, m).items()}
    h = prox.l1(0.01)
    x0 = gossip.stack_tree(jnp.zeros(10), m)
    problem = algorithm.Problem(loss, h, x0, data)
    mats = graphs.edge_matching_matrices(m)
    sched = graphs.MixingSchedule(tuple(mats), b=len(mats), eta=0.5,
                                  name="matching4")
    out = {"devices": len(jax.devices())}

    # auto prefers ppermute once a node-axis mesh is available.  Selection
    # is judged on the DSPG meta (one round/step): the m=4 matchings keep
    # offsets {0, 1, 3} — real band structure.  (DPSVRG's k_max=2 products
    # saturate all 4 offsets at m=4, so auto rightly picks dense there.)
    mesh = make_mesh((m,), ("nodes",))
    hp2 = dpsvrg.DSPGHyperParams(alpha0=0.3)
    meta2 = algorithm.dspg_algorithm(problem, hp2, 24).meta
    out["auto_with_mesh"] = transport.select_backend_name(sched, meta2, mesh)
    out["auto_without_mesh"] = transport.select_backend_name(sched, meta2)

    def hist_err(a, b):
        return float(np.max(np.abs(np.asarray(a.objective)
                                   - np.asarray(b.objective))))

    # dense vs ppermute history equivalence for DPSVRG multi-consensus
    # (saturated bands at m=4 — correctness must hold regardless), host and
    # scan paths
    hp = dpsvrg.DPSVRGHyperParams(alpha=0.3, beta=1.2, n0=3, num_outer=4,
                                  k_max=2)
    errs = {}
    for scan in (False, True):
        dense = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                           sched, exec=ExecSpec(scan=scan, gossip="dense"), seed=1, record_every=3)
        perm = runner.run(algorithm.dpsvrg_algorithm(problem, hp), problem,
                          sched, exec=ExecSpec(scan=scan, gossip="ppermute", mesh=mesh), seed=1, record_every=3)
        errs["scan" if scan else "host"] = hist_err(dense.history,
                                                    perm.history)
    out["errs"] = errs

    # DSPG flat loop (slot_start=1, one round/step, real band structure:
    # 2 point-to-point bands vs the dense m*(m-1) all-gather), with the
    # backend building its own mesh (mesh=None -> first m local devices)
    dense = runner.run(algorithm.dspg_algorithm(problem, hp2, 24), problem,
                       sched, exec=ExecSpec(gossip="dense"), seed=2, record_every=6)
    perm = runner.run(algorithm.dspg_algorithm(problem, hp2, 24), problem,
                      sched, exec=ExecSpec(gossip="ppermute"), seed=2, record_every=6)
    out["dspg_err"] = hist_err(dense.history, perm.history)
    out["wire_dense"] = int(dense.extras["wire_bytes"][-1])
    out["wire_ppermute"] = int(perm.extras["wire_bytes"][-1])

    # and on the static ring schedule (the paper's base topology)
    ring = graphs.b_connected_ring_schedule(m, b=1, seed=0)
    dense = runner.run(algorithm.dspg_algorithm(problem, hp2, 24), problem,
                       ring, exec=ExecSpec(gossip="dense"), seed=3, record_every=6)
    perm = runner.run(algorithm.dspg_algorithm(problem, hp2, 24), problem,
                      ring, exec=ExecSpec(gossip="ppermute", mesh=mesh), seed=3, record_every=6)
    out["ring_err"] = hist_err(dense.history, perm.history)
    print(json.dumps(out))
""")


def test_ppermute_matches_dense_on_four_device_mesh(run_multi_device):
    out = run_multi_device(_PPERMUTE_SCRIPT, devices=4)
    assert out["devices"] == 4
    assert out["auto_with_mesh"] == "ppermute"
    assert out["auto_without_mesh"] == "banded"
    assert out["errs"]["host"] < 1e-5, out
    assert out["errs"]["scan"] < 1e-5, out
    assert out["dspg_err"] < 1e-5, out
    assert out["ring_err"] < 1e-5, out
    # the whole point: fewer wire bytes than the dense all-gather
    assert out["wire_ppermute"] < out["wire_dense"], out


def test_ppermute_without_devices_raises_helpfully():
    """On the single-device main process, asking for ppermute must fail with
    the XLA_FLAGS hint, not a shape error deep inside shard_map."""
    import jax
    if len(jax.devices()) >= 4:
        pytest.skip("process has enough devices; error path not reachable")
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    algo = algorithm.dspg_algorithm(
        problem, dpsvrg.DSPGHyperParams(alpha0=0.3), num_steps=4)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        runner.run(algo, problem, _ring(4), exec=ExecSpec(gossip="ppermute"))


# ---------------------------------------------------------------------------
# CompressedPhi unit behaviour
# ---------------------------------------------------------------------------

def test_mix_with_state_requires_state_for_compressed():
    phi = compression.CompressedPhi(np.eye(2, dtype=np.float32), bits=8)
    tree = {"w": jnp.ones((2, 3))}
    with pytest.raises(ValueError, match="CompressionState"):
        compression.mix_with_state(phi, tree, None)
    mixed, st = compression.mix_with_state(
        phi, tree, compression.init_state(tree))
    np.testing.assert_allclose(np.asarray(mixed["w"]),
                               np.ones((2, 3)), atol=1e-6)


def test_mix_with_state_passthrough_stateless():
    tree = {"w": jnp.ones((2, 3))}
    mixed, st = compression.mix_with_state(np.eye(2), tree, None)
    assert st is None
    np.testing.assert_allclose(np.asarray(mixed["w"]), np.ones((2, 3)),
                               atol=1e-6)


def test_backend_mix_direct_use():
    """The protocol's ``mix`` entry point works standalone (what a bespoke
    trainer would call): stateless backends return the mixed tree, the
    compressed backend threads (tree, state) via its own init_mix_state."""
    data, h, x0 = _setup(m=6)
    sched = _ring(6)
    meta = transport.TransportMeta.constant(1)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)}
    ref = None
    for name in ("dense", "banded"):
        backend = transport.GOSSIP_BACKENDS[name]
        aux = backend.prepare(sched, meta)
        phi = backend.phi_for(aux, 0, 1)
        mixed = backend.mix(aux, phi, tree)["w"]
        if ref is None:
            ref = np.asarray(mixed)
        np.testing.assert_allclose(np.asarray(mixed), ref, atol=1e-6)
    comp = transport.GOSSIP_BACKENDS["compressed"]
    aux = comp.prepare(sched, meta)
    phi = comp.phi_for(aux, 0, 1)
    mstate = comp.init_mix_state(aux, tree)
    mixed, mstate = comp.mix(aux, phi, tree, mstate)
    np.testing.assert_allclose(np.asarray(mixed["w"]), ref, atol=0.05)
    with pytest.raises(ValueError, match="error-feedback"):
        comp.mix(aux, phi, tree)


# ---------------------------------------------------------------------------
# init_mix_state beyond DPSVRG: GT-SVRG and loopless ride compressed gossip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,args,kwargs", [
    ("gt_svrg", (0.1, 4, 10), {}),
    ("loopless_dpsvrg", (0.3, 40), {"snapshot_prob": 0.1,
                                    "consensus_rounds": 1}),
])
def test_gt_svrg_and_loopless_ride_compressed(name, args, kwargs):
    """Satellite smoke test: with init_mix_state extended beyond DPSVRG,
    every SVRG-family method converges under error-feedback compressed
    gossip on the paper logreg problem, tracking its uncompressed run."""
    m = 8
    ds = synthetic.make_paper_dataset("adult_like", scale=0.02, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in synthetic.partition_per_node(ds, m).items()}
    h = prox.l1(0.01)
    x0 = gossip.stack_tree(jnp.zeros(ds.dim), m)
    problem = _problem(data, h, x0)
    sched = _ring(m)
    full = runner.run(algorithm.ALGORITHMS[name](problem, *args, **kwargs),
                      problem, sched, exec=ExecSpec(scan=True, gossip="dense"), seed=0, record_every=5).history
    comp = runner.run(algorithm.ALGORITHMS[name](problem, *args, **kwargs),
                      problem, sched, exec=ExecSpec(scan=True, gossip="compressed"), seed=0, record_every=5).history
    descent = full.objective[0] - full.objective[-1]
    assert descent > 0
    assert comp.objective[-1] < comp.objective[0]
    assert abs(comp.objective[-1] - full.objective[-1]) < max(
        0.2 * descent, 5e-3)


# ---------------------------------------------------------------------------
# per-link byte maps (totals -> per-edge)
# ---------------------------------------------------------------------------

def test_bytes_per_link_sums_to_bytes_per_step():
    """The per-edge refinement must account exactly the same bytes as the
    scalar total, for every backend."""
    data, h, x0 = _setup(m=6)
    sched = _ring(6)
    meta = transport.TransportMeta.constant(1)
    pc = transport.node_param_count(x0)
    for name in ("dense", "banded"):
        backend = transport.GOSSIP_BACKENDS[name]
        aux = backend.prepare(sched, meta)
        phi = backend.phi_for(aux, 0, 1)
        links = backend.bytes_per_link(aux, phi, pc)
        assert sum(links.values()) == backend.bytes_per_step(aux, phi, pc)
        assert all(src != dst for src, dst in links)
    # bits=4 makes the per-link floors undershoot the single-floor total;
    # the remainder distribution must keep the sum EXACT
    for bits in (8, 4, 3):
        comp = transport.CompressedBackend(inner="banded", bits=bits)
        aux = comp.prepare(sched, meta)
        phi = comp.phi_for(aux, 0, 1)
        links = comp.bytes_per_link(aux, phi, pc)
        assert sum(links.values()) == comp.bytes_per_step(aux, phi, pc)


def test_bytes_per_link_topology():
    """On the ring, banded gossip only loads actual ring links (both
    directions of each active matching edge); dense loads every ordered
    pair regardless of sparsity."""
    data, h, x0 = _setup(m=6)
    m = 6
    sched = _ring(m)
    meta = transport.TransportMeta.constant(1)
    pc = transport.node_param_count(x0)
    dense = transport.GOSSIP_BACKENDS["dense"]
    aux_d = dense.prepare(sched, meta)
    links_d = dense.bytes_per_link(aux_d, dense.phi_for(aux_d, 0, 1), pc)
    assert len(links_d) == m * (m - 1)
    banded = transport.GOSSIP_BACKENDS["banded"]
    aux_b = banded.prepare(sched, meta)
    links_b = banded.bytes_per_link(aux_b, banded.phi_for(aux_b, 0, 1), pc)
    ring_links = {((i + 1) % m, i) for i in range(m)} | \
                 {(i, (i + 1) % m) for i in range(m)}
    assert set(links_b) <= ring_links
    assert len(links_b) < len(links_d)


def test_gt_svrg_wire_accounting_counts_both_payloads():
    """Gradient tracking gossips TWO quantities per round (iterate and
    tracker) with the same phi — AlgoMeta.gossip_payloads makes the wire
    accounting charge both, so at equal rounds GT-SVRG moves exactly 2x a
    single-payload method's bytes."""
    data, h, x0 = _setup()
    problem = _problem(data, h, x0)
    sched = _ring(4)
    gt = runner.run(algorithm.ALGORITHMS["gt_svrg"](problem, 0.1, 1, 5),
                    problem, sched, exec=ExecSpec(gossip="dense"), record_every=5)
    ds = runner.run(algorithm.dspg_algorithm(
        problem, dpsvrg.DSPGHyperParams(alpha0=0.3), num_steps=5),
        problem, sched, exec=ExecSpec(gossip="dense"), record_every=5)
    assert (gt.extras["wire_bytes"][-1]
            == 2 * ds.extras["wire_bytes"][-1])
