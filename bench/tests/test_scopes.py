"""bench/scopes.py: device time by the program's scopes, host self time and
arguments of its spans."""

import inspect
import re

import pytest

from bench import scopes

NS = 1e-9
BODY = "jit(exec_chunk)/while/body/closed_call"


def _op(name, start, end, path):
    return [f"%{name} = f32[] op()", start, end, path]


def _events():
    """A window of [100, 300) ns: a ``while`` container over grad, mix and
    prox ops, a snapshot ``cond`` whose body holds a grad op, an update op
    cut by the window's end and a mix op before it; host spans with a pull
    nested in the dispatch and a stage that opened before the window."""
    device = [
        _op("while.1", 90, 250, "jit(exec_chunk)/while"),
        _op("fusion.1", 100, 140, f"{BODY}/repro.grad/dot_general"),
        _op("fusion.2", 130, 150, f"{BODY}/repro.grad/vmap(jvp())/add"),
        _op("fusion.3", 150, 170, f"{BODY}/repro.mix/dot_general"),
        _op("fusion.4", 170, 180, f"{BODY}/repro.prox/max"),
        _op("cond.5", 190, 240, f"{BODY}/repro.snapshot/cond"),
        _op("fusion.6", 195, 230,
            f"{BODY}/repro.snapshot/cond/branch_1_fun/repro.grad/dot"),
        _op("fusion.7", 280, 320, f"{BODY}/repro.update/sub"),
        _op("fusion.8", 10, 20, f"{BODY}/repro.mix/dot_general"),
    ]
    host = [
        ["bench.window", 100, 300, {}, 0],
        ["repro.stage", 50, 110, {"run": 1, "h2d_bytes": 1000,
                                  "d2h_bytes": 0}, 0],
        ["repro.dispatch", 110, 200, {"run": 1, "dispatches": 3}, 0],
        ["repro.pull", 150, 160, {"run": 1, "d2h_bytes": 64}, 0],
        ["repro.pull", 170, 180, {"run": 1, "d2h_bytes": 36}, 0],
        ["repro.plan", 210, 260, {"run": 2, "steps": 5, "chunks": 2}, 1],
    ]
    return {"device": device, "host": host}


def test_scopes_take_the_union_of_their_outermost_ops_in_the_window():
    got = scopes.reduce(_events())
    s = got["scopes_s"]
    assert got["window_s"] == pytest.approx(200 * NS)
    # [100, 140) and [130, 150) overlap: the union is 50, not 60
    assert s["repro.grad"] == pytest.approx(50 * NS)
    # the cond and the grad in its body are snapshot time
    assert s["repro.snapshot"] == pytest.approx(50 * NS)
    assert s["repro.mix"] == pytest.approx(20 * NS)       # fusion.8 is out
    assert s["repro.prox"] == pytest.approx(10 * NS)
    assert s["repro.update"] == pytest.approx(20 * NS)    # cut at 300
    # busy: [100, 250) + [280, 300); the while's [180, 190) and
    # [240, 250) are covered by no scope
    assert got["busy_s"] == pytest.approx(170 * NS)
    assert got["unscoped_s"] == pytest.approx(20 * NS)


def test_spans_self_time_arguments_and_idle():
    got = scopes.reduce(_events())
    spans_s = got["spans_s"]
    assert spans_s["repro.dispatch"] == pytest.approx(70 * NS)   # 90 - 20
    assert spans_s["repro.pull"] == pytest.approx(20 * NS)
    assert spans_s["repro.stage"] == pytest.approx(10 * NS)     # clipped
    assert spans_s["repro.plan"] == pytest.approx(50 * NS)
    # arguments of the spans that start in the window; ``run`` is not summed
    assert got["span_args"] == {"repro.dispatch": {"dispatches": 3},
                                "repro.pull": {"d2h_bytes": 100},
                                "repro.plan": {"steps": 5, "chunks": 2}}
    assert got["span_count"] == {"repro.dispatch": 1, "repro.pull": 2,
                                 "repro.plan": 1}
    # idle [250, 280): the plan's self time covers [250, 260)
    assert got["idle_s"]["repro.plan"] == pytest.approx(10 * NS)
    assert got["idle_s"]["repro.dispatch"] == 0.0
    assert got["idle_unspanned_s"] == pytest.approx(20 * NS)


def test_per_step_numbers():
    got = scopes.per_step(scopes.reduce(_events()), steps=10)
    assert got["plan_us_per_step"] == pytest.approx(50e-3 / 10)
    assert got["dispatch_us_per_step"] == pytest.approx(70e-3 / 10)
    assert got["transfer_kb_per_step"] == pytest.approx(100 / 10 / 1e3)
    assert got["grad_us_per_step"] == pytest.approx(100e-3 / 10)
    assert got["opt_us_per_step"] == pytest.approx(50e-3 / 10)


def test_a_trace_without_the_names_gives_nothing():
    ev = {"device": [_op("fusion.1", 100, 140, "jit(exec_chunk)/while")],
          "host": [["bench.window", 100, 300, {}, 0]]}
    got = scopes.reduce(ev)
    assert got["scopes_s"] == {} and got["spans_s"] == {}
    assert got["unscoped_s"] == pytest.approx(40 * NS)
    assert scopes.per_step(got, steps=10) == {}


def test_names_are_the_programs_constants():
    from repro.core import spans
    assert scopes.SCOPES == spans.SCOPES
    assert scopes.SPANS == spans.SPANS
    used = set(re.findall(r'"(repro\.[a-z_]+)"',
                          inspect.getsource(scopes.per_step)))
    assert used == {spans.PLAN, spans.STAGE, spans.DISPATCH, spans.GRAD,
                    spans.SNAPSHOT, spans.UPDATE, spans.MIX, spans.PROX}


def test_transfer_kb_per_step_is_the_byte_count_from_shapes(tmp_path):
    """A resident DPSVRG job recorded on the CPU: the bytes its spans carry
    per step are the dataset's host copy, each step's staged inputs and the
    history, counted here from the shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner
    from repro.core.exec_spec import ExecSpec

    m, n, d, batch = 4, 40, 6, 2
    rng = np.random.default_rng(0)
    data = {"features": jnp.asarray(rng.normal(size=(m, n, d)),
                                    jnp.float32),
            "labels": jnp.asarray(rng.integers(0, 2, size=(m, n)),
                                  jnp.float32)}

    def loss(w, b):
        z = b["features"] @ w
        return jnp.mean(jnp.log1p(jnp.exp(z)) - b["labels"] * z)

    problem = algorithm.Problem(loss, prox.l1(0.01),
                                gossip.stack_tree(jnp.zeros(d), m), data)
    algo = algorithm.ALGORITHMS["dpsvrg"](problem, dpsvrg.DPSVRGHyperParams(
        alpha=0.1, beta=1.5, n0=2, num_outer=3, batch_size=batch))
    sched = graphs.b_connected_ring_schedule(m, b=1, seed=0)
    # record_every=1 cuts one-step chunks, which no bucket pads
    spec = ExecSpec(resident=True, gossip="dense")
    runner.run(algo, problem, sched, spec, seed=0, record_every=1)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(scopes.WINDOW_SPAN):
        res = runner.run(algo, problem, sched, spec, seed=1, record_every=1)
    jax.profiler.stop_trace()
    got = scopes.reduce(scopes.events(scopes.newest(tmp_path)))
    steps = int(res.history.steps[-1])
    dataset = m * n * (d + 1) * 4
    # features, labels, the dense mixing matrix, alpha, keep, and the
    # outer-transition flags (three bools and the round length)
    per_step = m * batch * (d + 1) * 4 + m * m * 4 + 4 + 1 + 3 + 4
    history = 4 * (2 * len(res.history.steps) + 1)
    want = (dataset + steps * per_step + history) / steps / 1e3
    assert scopes.per_step(got, steps)["transfer_kb_per_step"] == \
        pytest.approx(want, rel=1e-12)
    assert got["span_count"]["repro.stage"] == 2



def test_hlo_op_names_read_the_programs_metadata(tmp_path):
    """The trace's ``/host:metadata`` plane holds every loaded program's
    HLO, compiled before the trace or not; the reader finds the scope in
    its instructions' ``op_name``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("repro.grad"):
            return jnp.sin(x) @ x

    x = jnp.ones((8, 8))
    scoped(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    scoped(x).block_until_ready()
    jax.profiler.stop_trace()
    names = scopes.hlo_op_names(scopes.newest(tmp_path).read_bytes())
    programs = [p for p in names if p.startswith("jit_scoped(")]
    assert len(programs) == 1
    assert {scopes.scope_of(path) for path in names[programs[0]].values()
            if "sin" in path or "dot" in path} == {"repro.grad"}


FIXTURES = __import__("pathlib").Path(__file__).parent / "fixtures"


@pytest.mark.parametrize("cell,want", [
    ("paper.mnist8.dpsvrg", {"repro.grad", "repro.snapshot", "repro.mix",
                             "repro.record"}),
    ("lm.danube2.dpsvrg", {"repro.sample", "repro.grad", "repro.snapshot",
                           "repro.update", "repro.mix"}),
])
def test_a_chip_trace_names_its_ops_and_paths(cell, want):
    """A few hundred events of a ``--trace 1`` window on a TPU v5 lite,
    their paths resolved on the chip by ``events``: the op events are named
    by their HLO text, whose first word is the instruction that the
    programs' metadata maps to a path, and the paths hold the scopes."""
    import json
    fixture = json.loads((FIXTURES / f"trace_{cell}.json").read_text())
    assert fixture["device"] == "TPU v5 lite"
    ev = {"device": fixture["device_events"], "host": fixture["host_events"]}
    for name, _, _, path in ev["device"]:
        assert re.fullmatch(r"[\w.\-]+", scopes._instruction(name)), name
    # a few ops XLA makes on its own (the embedding gradient's scatter)
    # carry no metadata
    paths = [path for _, _, _, path in ev["device"] if path]
    assert len(paths) >= 0.9 * len(ev["device"])
    assert all(path.startswith(("jit(exec_chunk)/", "jit(record)/"))
               for path in paths)
    got = scopes.reduce(ev)
    assert set(got["scopes_s"]) >= want
    assert set(got["scopes_s"]) <= set(scopes.SCOPES)


def test_the_paper_chip_trace_holds_each_jobs_spans():
    import json
    fixture = json.loads(
        (FIXTURES / "trace_paper.mnist8.dpsvrg.json").read_text())
    jobs: dict = {}
    for name, _, _, args, _ in fixture["host_events"]:
        if name.startswith(scopes.PREFIX):
            jobs.setdefault(args["run"], []).append((name, args))
    assert len(jobs) == 4
    for spans in jobs.values():
        assert [n for n, _ in spans] == ["repro.stage", "repro.plan",
                                         "repro.stage", "repro.dispatch",
                                         "repro.pull"]
        # the dataset's host copy: 8 nodes x 7,500 rows x (784 + 1) f32
        assert spans[0][1]["d2h_bytes"] == 8 * 7500 * 785 * 4
        assert spans[1][1]["steps"] == 823
    got = scopes.reduce({"device": fixture["device_events"],
                         "host": fixture["host_events"]})
    per = scopes.per_step(got, fixture["steps"])
    assert per["transfer_kb_per_step"] == pytest.approx(254.49, abs=0.01)
