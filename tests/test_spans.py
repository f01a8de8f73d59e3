"""The program's names on the profiler's clock (core/spans.py): every
device scope lands in the compiled programs' ``op_name`` metadata, and a
CPU profiler trace of one resident job holds its host spans, once per job,
with one ``run`` id and byte arguments that add up to the transfer ledger."""

import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner, spans
from repro.core.exec_spec import ExecSpec
from repro.data.loader import LMLoader
from repro.models.api import ModelConfig
from repro.train import trainer

M, N, D = 4, 48, 6
TINY = ModelConfig(name="tiny-spans", arch_type="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                   vocab_size=64)
LM_PROX = prox.l1(1e-4)
TOKENS = np.random.default_rng(0).integers(0, 64, size=2400).astype(np.int32)


def logreg_loss(w, batch):
    logits = batch["features"] @ w
    y = batch["labels"]
    return jnp.mean(-y * logits + jnp.log1p(jnp.exp(logits)))


def _problem():
    rng = np.random.default_rng(0)
    data = {"features": jnp.asarray(rng.normal(size=(M, N, D)), jnp.float32),
            "labels": jnp.asarray(rng.integers(0, 2, size=(M, N)),
                                  jnp.float32)}
    return algorithm.Problem(logreg_loss, prox.l1(0.01),
                             gossip.stack_tree(jnp.zeros(D), M), data)


def _dpsvrg(problem):
    return algorithm.ALGORITHMS["dpsvrg"](
        problem, dpsvrg.DPSVRGHyperParams(alpha=0.2, beta=1.5, n0=2,
                                          num_outer=3, batch_size=2))


def _sched():
    return graphs.b_connected_ring_schedule(M, b=1, seed=0)


def _scopes_of(hlo: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/") if part.startswith("repro.")}


@pytest.fixture
def compiled(monkeypatch):
    """The compiled HLO text of every executor the runner's executable
    cache hands out, by cache kind, taken at its first call."""
    texts: dict = {}
    shared = runner._shared_exec

    def spy(key, make):
        fn = shared(key, make)

        def call(*args):
            if key[0] not in texts:
                texts[key[0]] = fn.lower(*args).compile().as_text()
            return fn(*args)

        return call

    monkeypatch.setattr(runner, "_shared_exec", spy)
    return texts


PAPER_SCOPES = {spans.GRAD, spans.SNAPSHOT, spans.UPDATE, spans.MIX,
                spans.PROX}


@pytest.mark.parametrize("sampling,kernel,want", [
    ("host", "xla", PAPER_SCOPES),
    ("device", "xla", PAPER_SCOPES | {spans.SAMPLE}),
    ("host", "pallas", {spans.GRAD, spans.SNAPSHOT, spans.FUSED_UPDATE}),
])
def test_resident_paper_chunk_and_record_kernel_carry_their_scopes(
        compiled, sampling, kernel, want):
    problem = _problem()
    runner.run(_dpsvrg(problem), problem, _sched(),
               ExecSpec(resident=True, sampling=sampling, kernel=kernel,
                        gossip="dense"), seed=0, record_every=4)
    assert _scopes_of(compiled["resident"]) >= want
    assert _scopes_of(compiled["record"]) == {spans.RECORD}


@pytest.mark.parametrize("algorithm_name", ["dpsvrg", "dspg"])
def test_lm_chunk_carries_its_scopes(compiled, algorithm_name):
    tc = trainer.TrainerConfig(num_steps=6, snapshot_every=3, log_every=3,
                               alpha=0.05, consensus_rounds=2,
                               algorithm=algorithm_name, resident=True)
    loader = LMLoader(TOKENS, num_nodes=M, per_node_batch=2, seq_len=16,
                      seed=1)
    trainer.train_loop(TINY, LM_PROX, graphs.b_connected_ring_schedule(
        M, b=2, seed=0), loader, tc)
    want = {spans.SAMPLE, spans.GRAD, spans.UPDATE, spans.MIX, spans.PROX}
    if algorithm_name == "dpsvrg":
        want.add(spans.SNAPSHOT)
    assert _scopes_of(compiled["lm_resident"]) == want


def _trace(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace -> (its result, the repro.*
    host spans as (name, start, end, args) in start order)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = next(pathlib.Path(tmp_path).glob("**/*.xplane.pb"))
    found = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        found.append((ev.name, ev.start_ns, ev.end_ns,
                                      dict(ev.stats)))
    return out, sorted(found, key=lambda s: s[1])


def _sums(found, key):
    return sum(args.get(key, 0) for _, _, _, args in found)


def test_runner_job_spans_once_each_with_one_run_id(tmp_path):
    problem = _problem()
    algo = _dpsvrg(problem)
    spec = ExecSpec(resident=True, gossip="dense")
    runner.run(algo, problem, _sched(), spec, seed=0, record_every=4)
    res, found = _trace(tmp_path, lambda: runner.run(
        algo, problem, _sched(), spec, seed=1, record_every=4))
    names = [n for n, _, _, _ in found]
    # the dataset's host copy, planning, staging, the op loop, the history
    assert names == [spans.STAGE, spans.PLAN, spans.STAGE, spans.DISPATCH,
                     spans.PULL]
    assert len({args["run"] for _, _, _, args in found}) == 1
    stage_end = max(e for n, _, e, _ in found if n == spans.STAGE)
    dispatch = next(s for n, s, _, _ in found if n == spans.DISPATCH)
    assert stage_end <= dispatch
    plan = next(args for n, _, _, args in found if n == spans.PLAN)
    assert plan["steps"] == res.history.steps[-1]
    assert plan["chunks"] > 0
    assert _sums(found, "h2d_bytes") == res.extras["bytes_h2d"] > 0
    assert _sums(found, "d2h_bytes") == res.extras["bytes_d2h"] > 0
    # the pull holds the two history buffers and the slot counter
    pull = next(args for n, _, _, args in found if n == spans.PULL)
    assert pull["d2h_bytes"] == 4 * (2 * len(res.history.steps) + 1)


def test_each_job_gets_its_own_run_id(tmp_path):
    problem = _problem()
    algo = _dpsvrg(problem)
    spec = ExecSpec(resident=True, sampling="device", gossip="dense")

    def two():
        for seed in (0, 1):
            runner.run(algo, problem, _sched(), spec, seed=seed,
                       record_every=4)

    _, found = _trace(tmp_path, two)
    # device sampling stages no host copy of the dataset
    assert [n for n, _, _, _ in found] == \
        [spans.PLAN, spans.STAGE, spans.DISPATCH, spans.PULL] * 2
    runs = [args["run"] for _, _, _, args in found]
    assert len(set(runs[:4])) == len(set(runs[4:])) == 1
    assert runs[0] != runs[4]


def test_train_loop_spans_pulls_nest_in_dispatch(tmp_path):
    tc = trainer.TrainerConfig(num_steps=9, snapshot_every=4, log_every=4,
                               alpha=0.05, consensus_rounds=2,
                               resident=True, ckpt_dir=str(tmp_path / "ck"),
                               ckpt_every=4)

    def job():
        loader = LMLoader(TOKENS, num_nodes=M, per_node_batch=2, seq_len=16,
                          seed=1)
        return trainer.train_loop(TINY, LM_PROX,
                                  graphs.b_connected_ring_schedule(
                                      M, b=2, seed=0), loader, tc)

    job()
    hist, found = _trace(tmp_path / "trace", job)
    names = [n for n, _, _, _ in found]
    assert names[:3] == [spans.PLAN, spans.STAGE, spans.DISPATCH]
    assert names.count(spans.PLAN) == names.count(spans.STAGE) == \
        names.count(spans.DISPATCH) == 1
    assert names.count(spans.PULL) == len(hist["step"])
    # periodic saves at steps 4 and 8 inside the op loop, the final one after
    assert names.count(spans.CKPT) == 3
    assert len({args["run"] for _, _, _, args in found}) == 1
    _, d0, d1, dargs = found[2]
    assert dargs["dispatches"] > 0
    inside = [n for n, s, e, _ in found[3:] if d0 <= s and e <= d1]
    assert inside.count(spans.PULL) == len(hist["step"])
    assert inside.count(spans.CKPT) == 2
    plan = found[0][3]
    assert plan["steps"] == tc.num_steps and plan["chunks"] == dargs[
        "dispatches"]
    ledger = hist["transfers"]
    assert _sums(found, "h2d_bytes") == ledger["bytes_h2d"] > 0
    assert _sums(found, "d2h_bytes") == ledger["bytes_d2h"] > 0


def test_host_and_scan_paths_count_bytes_too():
    problem = _problem()
    algo = _dpsvrg(problem)
    for spec in (None, ExecSpec(scan=True, gossip="dense")):
        res = runner.run(algo, problem, _sched(), spec, seed=0,
                         record_every=4)
        assert res.extras["bytes_h2d"] > 0 and res.extras["bytes_d2h"] > 0
        # one host copy of the dataset at least
        assert res.extras["bytes_d2h"] >= 4 * M * N * (D + 1)

