"""Bring-up check: the three drivers, end to end, on a TPU at real sizes.

    python chip_smoke.py              # one chip: phases paper, train, serve
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Every phase runs in this one process and goes through the entry points a
user calls:

* paper — ``runner.run`` resident DPSVRG and DSPG on the full-size
  ``mnist_like`` problem (n=60,000, d=784, m=8) against the host loop run
  on the CPU device of this process; then loopless DPSVRG on an LM-sized
  (8, 131072) stack with ``kernel="pallas"`` against ``kernel="xla"``.
* train — ``repro.launch.train`` on h2o-danube-1.8b at its published
  widths, cut to 2 layers, m=2 nodes, seq 512, with a snapshot refresh
  inside the run and a checkpoint at the end.  The loss must be finite
  and fall.
* serve — ``repro.launch.serve`` from that checkpoint's consensus
  average, ``ResidentEngine`` with flash-attention prefill; every token
  must equal the host ``ContinuousBatcher``'s.

``--chips 4`` runs resident DPSVRG with ``gossip="ppermute"`` and
``shard="nodes"`` on a 4-chip node mesh against ``gossip="dense"`` on one
chip, and a 4-cell λ×seed sweep with ``shard="cells"`` against the
unsharded sweep.

Without a TPU the script exits non-zero before any phase.  Each phase
prints the device kind, compile and wall seconds, the device's
``peak_bytes_in_use`` so far, and whether its compiled programs hold a
``tpu_custom_call`` where a kernel is expected.  None of these are speed
measurements.  The last line is the JSON result.

The phase functions take their sizes as keywords, so each can be
rehearsed at a tiny size on the CPU by importing this module and calling
it; ``main`` runs only the real sizes, and only on a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit("chip_smoke.py: src/repro is missing; run it from a checkout")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import logreg_loss  # noqa: E402  (the figures' loss)

# the tolerance tests/test_runner_resident.py holds resident runs to
OBJ_TOL = dict(rtol=1e-4, atol=1e-6)
CONS_TOL = dict(rtol=1e-3, atol=1e-6)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# per-phase instrumentation
# ---------------------------------------------------------------------------

_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str, work: pathlib.Path, kernel_module: str | None = None):
    """Time a phase; with ``kernel_module`` set on a TPU, dump every program
    the phase compiles and require that each compiled program whose name
    holds ``kernel_module`` contains a ``tpu_custom_call``.  (Off the TPU,
    as in a CPU rehearsal of a phase, no kernel is compiled in.)"""
    if jax.default_backend() != "tpu":
        kernel_module = None
    dump = work / f"ir_{name}"
    if kernel_module:
        jax.config.update("jax_dump_ir_to", str(dump))
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", "")
    wall = time.perf_counter() - t0
    kernel = "not expected"
    if kernel_module:
        progs = sorted(dump.glob(f"*{kernel_module}*_compile.mlir"))
        if not progs:
            raise AssertionError(f"{name}: no {kernel_module} program was "
                                 f"compiled")
        held = [("tpu_custom_call" in p.read_text()) for p in progs]
        kernel = f"{sum(held)}/{len(held)} {kernel_module} programs"
        if not all(held):
            raise AssertionError(f"{name}: a compiled {kernel_module} "
                                 f"program holds no tpu_custom_call")
    log(f"phase={name} device_kind={jax.devices()[0].device_kind!r} "
        f"compile_s={_compile_s[0] - c0:.3f} wall_s={wall:.3f} "
        f"peak_bytes_in_use={_peak_bytes()} tpu_custom_call={kernel}")


def _check_close(name: str, got, ref) -> None:
    g, r = got.history, ref.history
    for field in ("epochs", "comm_rounds", "steps"):
        np.testing.assert_array_equal(getattr(g, field), getattr(r, field),
                                      err_msg=f"{name}: {field}")
    d_obj = float(np.max(np.abs(g.objective - r.objective)))
    d_con = float(np.max(np.abs(g.consensus - r.consensus)))
    log(f"{name}: {len(g.objective)} records, final objective "
        f"{g.objective[-1]!r} vs {r.objective[-1]!r}, max |diff| objective "
        f"{d_obj!r} consensus {d_con!r}")
    np.testing.assert_allclose(g.objective, r.objective, **OBJ_TOL,
                               err_msg=f"{name}: objective")
    np.testing.assert_allclose(g.consensus, r.consensus, **CONS_TOL,
                               err_msg=f"{name}: consensus")


# ---------------------------------------------------------------------------
# the paper problem
# ---------------------------------------------------------------------------

def _paper_data(m: int, scale: float):
    from repro.data import synthetic
    ds = synthetic.make_paper_dataset("mnist_like", scale=scale)
    return synthetic.partition_per_node(ds, m), ds.dim


def _problem(data_np, dim: int, m: int, lam: float = 0.01):
    """Build the problem on the current default device."""
    from repro.core import algorithm, gossip, prox
    data = {k: jnp.asarray(v) for k, v in data_np.items()}
    x0 = gossip.stack_tree(jnp.zeros(dim, jnp.float32), m)
    return algorithm.Problem(logreg_loss, prox.l1(lam), x0, data)


def _dpsvrg(problem):
    from repro.core import algorithm, dpsvrg
    return algorithm.ALGORITHMS["dpsvrg"](
        problem, dpsvrg.DPSVRGHyperParams(alpha=0.2, beta=1.2, n0=8,
                                          num_outer=4))


def _dspg(problem, steps: int):
    from repro.core import algorithm, dpsvrg
    return algorithm.ALGORITHMS["dspg"](
        problem, dpsvrg.DSPGHyperParams(alpha0=0.2), steps)


def run_paper(work: pathlib.Path, *, scale: float = 1.0,
              lm_d: int = 131072) -> None:
    from repro.core import graphs, runner, transport
    from repro.core.exec_spec import ExecSpec

    m = 8
    data_np, dim = _paper_data(m, scale)
    sched = graphs.b_connected_ring_schedule(m, b=2, seed=0)
    cpu = jax.devices("cpu")[0]
    log(f"paper problem: mnist_like n={m * data_np['labels'].shape[1]} "
        f"d={dim} m={m}, schedule {sched.name}")

    for name in ("dpsvrg", "dspg"):
        with phase(f"paper.{name}", work):
            problem = _problem(data_np, dim, m)
            algo = (_dpsvrg(problem) if name == "dpsvrg"
                    else _dspg(problem, 60))
            log(f"paper.{name}: gossip "
                f"{transport.select_backend_name(sched, algo.meta)}")
            got = runner.run(algo, problem, sched, ExecSpec(resident=True),
                             seed=0, record_every=5)
            with jax.default_device(cpu):
                problem_ref = _problem(data_np, dim, m)
                algo_ref = (_dpsvrg(problem_ref) if name == "dpsvrg"
                            else _dspg(problem_ref, 60))
                ref = runner.run(algo_ref, problem_ref, sched, ExecSpec(),
                                 seed=0, record_every=5)
            _check_close(f"paper.{name} resident vs host loop on cpu",
                         got, ref)

    # LM-sized stack through the fused kernel (benchmarks/kernel_bench.py's
    # large_d case): loopless DPSVRG on a static 5-band circulant ring
    from repro.core import algorithm, gossip, prox
    w = np.zeros((m, m))
    for off, c in ((0, 0.4), (1, 0.2), (-1, 0.2), (2, 0.1), (-2, 0.1)):
        w[np.arange(m), (np.arange(m) + off) % m] += c
    circ = graphs.static_schedule(w, name="circulant8_5band")
    rng = np.random.default_rng(0)
    data = {"features": jnp.asarray(
        rng.normal(size=(m, 4, lm_d)) / np.sqrt(lm_d), jnp.float32),
        "labels": jnp.asarray(
            rng.integers(0, 2, size=(m, 4)) * 2.0 - 1.0, jnp.float32)}
    problem = algorithm.Problem(logreg_loss, prox.l1(0.01),
                                gossip.stack_tree(jnp.zeros(lm_d), m), data)

    def loopless():
        return algorithm.loopless_dpsvrg_algorithm(
            problem, 0.05, 40, consensus_rounds=1, batch_size=1)

    spec = ExecSpec(resident=True, gossip="banded")
    hist = {}
    for kernel in ("xla", "pallas"):
        module = "exec_chunk" if kernel == "pallas" else None
        with phase(f"paper.fused_{kernel}", work, module):
            hist[kernel] = runner.run(loopless(), problem, circ,
                                      spec.replace(kernel=kernel), seed=0,
                                      record_every=10)
    _check_close(f"paper.fused (m={m}, d={lm_d}) pallas vs xla",
                 hist["pallas"], hist["xla"])


# ---------------------------------------------------------------------------
# the LM trainer and the server
# ---------------------------------------------------------------------------

ARCH = "h2o-danube-1.8b"


def run_train(work: pathlib.Path, *, layers: int = 2, seq_len: int = 512,
              alpha: float = 0.002) -> str:
    from repro.launch import train
    ckpt = work / "ckpt"
    with phase("train", work):
        hist = train.main([
            "--arch", ARCH, "--layers", str(layers), "--nodes", "2",
            "--steps", "6", "--seq-len", str(seq_len),
            "--snapshot-every", "3", "--alpha", str(alpha), "--ckpt-dir", str(ckpt)])
    loss = np.asarray(hist["loss"])
    log(f"train: loss {loss.tolist()!r}")
    if not np.all(np.isfinite(loss)):
        raise AssertionError(f"train: non-finite loss {loss.tolist()}")
    if not loss[-1] < loss[0]:
        raise AssertionError(f"train: loss did not fall: {loss.tolist()}")
    return str(ckpt)


def run_serve(work: pathlib.Path, ckpt: str, *, layers: int = 2) -> None:
    from repro.launch import serve
    requests, new = 6, 16
    with phase("serve", work, "prefill"):
        out = serve.main([
            "--arch", ARCH, "--layers", str(layers), "--flash",
            "--ckpt-dir", ckpt, "--engine", "resident", "--slots", "4",
            "--max-len", "128", "--requests", str(requests),
            "--prompt-len", "100", "--new", str(new), "--verify-host"])
    lens = sorted(len(v) for v in out["outputs"].values())
    if lens != [new] * requests:
        raise AssertionError(f"serve: output lengths {lens}")


# ---------------------------------------------------------------------------
# four chips: the paths that exist only across chips
# ---------------------------------------------------------------------------

def run_four_chips(work: pathlib.Path, *, scale: float = 1.0) -> None:
    from repro.core import graphs, prox, runner, sweep
    from repro.core.exec_spec import ExecSpec
    from repro.core.mesh import make_mesh

    m = 4
    data_np, dim = _paper_data(m, scale)
    sched = graphs.b_connected_ring_schedule(m, b=2, seed=0)
    mesh = make_mesh((m,), ("nodes",))

    with phase("chips4.ppermute_nodes", work):
        problem = _problem(data_np, dim, m)
        got = runner.run(_dpsvrg(problem), problem, sched,
                         ExecSpec(resident=True, gossip="ppermute",
                                  mesh=mesh, shard="nodes"),
                         seed=0, record_every=5)
        ref = runner.run(_dpsvrg(problem), problem, sched,
                         ExecSpec(resident=True, gossip="dense"),
                         seed=0, record_every=5)
        leaf = jax.tree.leaves(got.params)[0]
        log(f"ppermute params sharded over {len(leaf.sharding.device_set)} "
            f"devices")
    _check_close("chips4 ppermute+shard=nodes vs dense on one chip",
                 got, ref)

    def build(lam=0.01):
        problem = _problem(data_np, dim, m, lam)
        return _dpsvrg(problem), problem

    grid = {"lam": [0.003, 0.01], "seed": [0, 1]}
    with phase("chips4.sweep_cells", work):
        plain = sweep.run_sweep(build, grid, sched,
                                ExecSpec(resident=True, gossip="dense"),
                                record_every=5)
        sharded = sweep.run_sweep(
            build, grid, sched,
            ExecSpec(resident=True, gossip="dense", shard="cells"),
            record_every=5)
    for i in range(4):
        _check_close(f"chips4 shard=cells cell {i} vs unsharded",
                     sharded.cell(i), plain.cell(i))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind!r}, "
        f"jax {jax.__version__}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = pathlib.Path(tmp)
        if args.chips == 4:
            run_four_chips(work)
        else:
            run_paper(work)
            run_serve(work, run_train(work))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
