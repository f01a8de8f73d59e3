"""Driver: the paper's DPSVRG job through ``runner.run`` (resident).

Set-up builds the configuration's data set from the seed on the host, puts
it on the device, and runs one whole job to compile every chunk variant and
the record kernel.  The window then repeats whole jobs, each on its own
seed (the same sizes, another draw of rows), with planning, staging, the
chunks, the records and the history pull inside; the job in flight at the
deadline finishes and is counted.  A sample of the window's jobs, drawn
from the seed, is recomputed by the plain reference once the window has
closed.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import Check, Outcome, log
from bench.reference import paper as ref_paper
from bench.seeds import derive
from bench.traffic import mnist_like

CHECK_JOBS = 3                  # window jobs the reference recomputes


def compare(got: dict, want: dict, limits: dict) -> list:
    """The numbers a job is judged by, each beside its limit: the widest
    relative gap of the recorded objective; the widest gap of the recorded
    consensus against the largest consensus of the reference's history (it
    starts at 0 and ends near round-off, where a relative gap says
    nothing); the widest relative gap of a node's final iterate, against
    the larger of its norm and the median node's; and the count of history
    entries (epochs, gossip rounds, steps) that differ."""
    obj = np.max(np.abs(got["objective"] - want["objective"])
                 / np.abs(want["objective"]))
    cons = np.max(np.abs(got["consensus"] - want["consensus"])) \
        / max(float(np.max(want["consensus"])), 1e-30)
    xr = np.linalg.norm(want["params"], axis=1)
    xfloor = max(float(np.median(xr)), 1e-30)
    x = np.max(np.linalg.norm(got["params"] - want["params"], axis=1)
               / np.maximum(xr, xfloor))
    counts = sum(int(np.sum(np.asarray(got[k]) != np.asarray(want[k])))
                 if np.shape(got[k]) == np.shape(want[k]) else 1
                 for k in ("epochs", "comm_rounds", "steps"))
    return [Check("objective", float(obj), limits["objective"]),
            Check("consensus", float(cons), limits["consensus"]),
            Check("params", float(x), limits["params"]),
            Check("columns", float(counts), 0.0)]


def as_answer(result) -> dict:
    h = result.history
    return {"objective": np.asarray(h.objective, np.float64),
            "consensus": np.asarray(h.consensus, np.float64),
            "epochs": np.asarray(h.epochs), "steps": np.asarray(h.steps),
            "comm_rounds": np.asarray(h.comm_rounds),
            "params": np.asarray(result.params, np.float64)}


@dataclasses.dataclass
class Setup:
    """What one ``--seed`` makes: the data set and the job's pieces."""
    config: dict
    job: dict
    seeds: dict
    host: dict
    problem: object

    @classmethod
    def build(cls, config: dict, job: dict, seed: int) -> "Setup":
        import jax.numpy as jnp
        from repro.core import algorithm, gossip, prox
        seeds = derive(seed, ("data", "warm", "jobs", "sample"))
        m = config["nodes"]
        host = mnist_like.make_problem_data(config["problem"], m,
                                            seeds["data"])
        data = {k: jnp.asarray(v) for k, v in host.items()}
        x0 = gossip.stack_tree(
            jnp.zeros(host["features"].shape[2], jnp.float32), m)
        problem = algorithm.Problem(mnist_like.logreg_loss,
                                    prox.l1(config["problem"]["l1"]), x0,
                                    data)
        return cls(config, job, seeds, host, problem)

    def spec(self):
        from repro.core.exec_spec import ExecSpec
        job, m = self.job, self.config["nodes"]
        if job["gossip"] == "ppermute":
            from repro.core.mesh import make_mesh
            return ExecSpec(resident=True, gossip="ppermute",
                            mesh=make_mesh((m,), ("nodes",)), shard="nodes")
        return ExecSpec(resident=True, gossip=job["gossip"])

    def program_job(self, seed: int, spec=None):
        """One whole ``runner.run`` job on this data set."""
        from repro.core import algorithm, dpsvrg, graphs, runner
        job = self.job
        hp = dpsvrg.DPSVRGHyperParams(alpha=job["alpha"], beta=job["beta"],
                                      n0=job["n0"],
                                      num_outer=job["num_outer"],
                                      batch_size=job["batch"])
        sched = graphs.b_connected_ring_schedule(
            self.config["nodes"], b=job["schedule_b"], seed=0)
        algo = algorithm.ALGORITHMS["dpsvrg"](self.problem, hp)
        return runner.run(algo, self.problem, sched, spec or self.spec(),
                          seed=seed, record_every=job["record_every"])

    def reference_job(self, seed: int, precision="highest", fault=None):
        return ref_paper.run_job(self.host, self.config["problem"], self.job,
                                 seed, precision=precision, fault=fault)


def run(cell) -> Outcome:
    job = cell.workload["job"]
    setup = Setup.build(cell.config, job, cell.seed)
    spec = setup.spec()

    log(f"warm-up job, gossip {job['gossip']}")
    steps_per_job = int(setup.program_job(setup.seeds["warm"],
                                          spec).history.steps[-1])
    log(f"{steps_per_job} steps a job")

    window = cell.window
    seconds = min(cell.seconds, job["trace_seconds"]) if window.trace \
        else cell.seconds
    job_seeds = np.random.default_rng(setup.seeds["jobs"])
    answers, seeds_run = [], []
    window.open()
    while not answers or time.perf_counter() - window.t_open < seconds:
        s = int(job_seeds.integers(0, 2**31 - 1))
        answers.append(setup.program_job(s, spec))
        seeds_run.append(s)
    window.close()
    jobs = len(answers)
    steps = jobs * steps_per_job
    answers = [as_answer(r) for r in answers]
    failed = sum(not np.all(np.isfinite(a["objective"])) for a in answers)
    log(f"window: {jobs} jobs, {steps} steps in {window.seconds:.3f} s")

    def check() -> list:
        pick = np.random.default_rng(setup.seeds["sample"])
        chosen = sorted(pick.choice(jobs, size=min(CHECK_JOBS, jobs),
                                    replace=False).tolist())
        worst: dict = {}
        for j in chosen:
            want = setup.reference_job(seeds_run[j])
            for c in compare(answers[j], want, cell.workload["limits"]):
                if c.name not in worst or not c.value <= worst[c.name].value:
                    worst[c.name] = c
            log(f"job {j}: final objective {answers[j]['objective'][-1]!r} "
                f"reference {want['objective'][-1]!r}")
        return list(worst.values())

    def release():
        setup.problem.full_data.clear()

    return Outcome(
        attempted=jobs, failed=int(failed), steps=steps,
        end_to_end={"paper_step_ms": 1e3 * window.seconds / steps},
        counts={"steps_per_job": steps_per_job},
        check=check, release=release)
