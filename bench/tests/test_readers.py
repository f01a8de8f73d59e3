"""The per-layer readers on two recorded chip traces (``--trace 1``
windows of the DPSVRG cells on a TPU v5 lite, a few hundred events each,
in ``fixtures/``): the readers of the program's scopes and spans give what
bench/scopes.py gives per step, the readers of bench/trace.py's summary
read what they read before the breakdown counted each op by its own time,
and that breakdown leaves no container on top with its body's time."""

import json
import pathlib

import pytest

from bench import harness, scopes, trace
from bench.drivers import lm_train

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
LM, PAPER = "lm.danube2.dpsvrg", "paper.mnist8.dpsvrg"

# each reader's value on each fixture, read with bench/trace.py as it was
# before ``self_times`` (the parent of the change that added it)
BEFORE = {
    (LM, "mfu.train"): 25.943836044253537,
    (LM, "device_idle.train"): 0.8536649758739911,
    (LM, "chunk_us_per_step.paper"): 217346.13574,
    (LM, "record_us_per_step.paper"): None,
    (LM, "device_idle.paper"): 0.8536649758739911,
    (LM, "collective_us_per_step.paper"): None,
    (PAPER, "mfu.train"): None,
    (PAPER, "device_idle.train"): 96.14573067077144,
    (PAPER, "chunk_us_per_step.paper"): 41.27722721749697,
    (PAPER, "record_us_per_step.paper"): 0.30604738760631833,
    (PAPER, "device_idle.paper"): 96.14573067077144,
    (PAPER, "collective_us_per_step.paper"): None,
}
TOTALS = {
    LM: {"window_s": 10.960875946, "busy_s": 10.867306787,
         "modules_s": {"exec_chunk": 10.867306787}, "collective_s": 0.0,
         "collective_exposed_s": 0.0},
    PAPER: {"window_s": 3.5517014590000002, "busy_s": 0.13689214,
            "modules_s": {"exec_chunk": 0.13588463200000003,
                          "record": 0.001007508},
            "collective_s": 0.0, "collective_exposed_s": 0.0},
}


def _fixture(cell: str) -> dict:
    return json.loads((FIXTURES / f"trace_{cell}.json").read_text())


def _programs(device_events) -> list:
    """``XLA Modules`` events, which the fixtures do not keep: one per run
    of outermost ops, named after the jitted function of its path."""
    out, end = [], None
    for _, s, e, path in sorted(device_events, key=lambda o: (o[1], -o[2])):
        if end is None or s >= end:
            name = path.split("/")[0].replace("jit(", "jit_").rstrip(")")
            out.append((f"{name}(1)", s, e))
            end = e
        else:
            end = max(end, e)
    return out


def _raw(fixture: dict) -> dict:
    """The fixture as bench/trace.py's ``load`` gives a trace."""
    ops = fixture["device_events"]
    return {"devices": {0: {"XLA Ops": [(n, s, e) for n, s, e, _ in ops],
                            "XLA Modules": _programs(ops)}},
            "host": [(n, s, e) for n, s, e, _, _ in fixture["host_events"]]}


def _counts(cell: str) -> dict:
    workload = harness.load_workload(cell)
    if workload["driver"] == "lm_train":
        config = harness.load_config(workload["config"])
        return {"flops_per_step": lm_train.flops_per_step(config,
                                                          workload["job"])}
    return {"steps_per_job": 823}


def _ctx(cell: str) -> dict:
    """What the harness hands the readers after a traced run."""
    fixture = _fixture(cell)
    summary = trace.reduce(_raw(fixture), chips=1)
    named = scopes.reduce({"device": fixture["device_events"],
                           "host": fixture["host_events"]})
    return {"workload": harness.load_workload(cell),
            "steps": fixture["steps"], "window_s": summary["window_s"],
            "trace": summary, "scopes": scopes.per_step(named,
                                                        fixture["steps"]),
            "counts": _counts(cell), "chips": 1,
            "peaks": harness.load_peaks(fixture["device"])}


@pytest.mark.parametrize("cell,reader", sorted(BEFORE))
def test_existing_reader_reads_what_it_read_before(cell, reader):
    got = harness.load_reader(reader)(_ctx(cell))
    want = BEFORE[cell, reader]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("cell", [LM, PAPER])
def test_summary_totals_are_what_they_were(cell):
    got = trace.reduce(_raw(_fixture(cell)), chips=1)
    want = TOTALS[cell]
    for key in ("window_s", "busy_s", "collective_s",
                "collective_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-12), key
    assert got["modules_s"] == pytest.approx(want["modules_s"], rel=1e-12)


@pytest.mark.parametrize("cell,reader,key", [
    (LM, "grad_us_per_step.train", "grad_us_per_step"),
    (LM, "opt_us_per_step.train", "opt_us_per_step"),
    (PAPER, "plan_us_per_step.paper", "plan_us_per_step"),
    (PAPER, "dispatch_us_per_step.paper", "dispatch_us_per_step"),
])
def test_scope_reader_gives_the_per_step_number(cell, reader, key):
    fixture = _fixture(cell)
    want = scopes.per_step(scopes.reduce({"device": fixture["device_events"],
                                          "host": fixture["host_events"]}),
                           fixture["steps"])[key]
    got = harness.load_reader(reader)(_ctx(cell))
    assert got is not None and got > 0
    assert got == want


def _own_and_whole(fixture: dict):
    """Each op name's own seconds in the window, and its whole seconds."""
    lo, hi = next((s, e) for n, s, e, _, _ in fixture["host_events"]
                  if n == trace.WINDOW_SPAN)
    own = trace.self_times(_raw(fixture)["devices"][0]["XLA Ops"], lo, hi)
    whole: dict = {}
    for name, start, end, _ in fixture["device_events"]:
        part = max(0.0, min(end, hi) - max(start, lo)) * 1e-9
        whole[name] = whole.get(name, 0.0) + part
    return own, whole


@pytest.mark.parametrize("cell", [LM, PAPER])
def test_breakdown_counts_each_op_by_its_own_time(cell):
    """Every op of the fixtures nests in or follows another, so the ops' own
    times add up to the busy time, and the breakdown lists own times."""
    fixture = _fixture(cell)
    got = trace.reduce(_raw(fixture), chips=1)
    own, whole = _own_and_whole(fixture)
    assert sum(own.values()) == pytest.approx(got["busy_s"], rel=1e-9)
    assert all(own[n] <= whole[n] * (1 + 1e-12) for n in own)
    ops = dict(got["breakdown"]["device_ops"])
    assert set(ops) <= set(own)
    for name, seconds in ops.items():
        assert seconds == own[name]


def test_the_training_loop_keeps_only_what_its_body_leaves():
    """The LM chunk's ``while`` spans the whole window; the parent's
    breakdown put it on top with all 10.87 s of its body's ops."""
    own, whole = _own_and_whole(_fixture(LM))
    assert whole["%while.500 = ..."] == pytest.approx(10.867306787)
    assert own["%while.500 = ..."] < 0.6 * whole["%while.500 = ..."]
