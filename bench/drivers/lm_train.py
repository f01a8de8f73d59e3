"""Driver: decentralized LM training through ``train_loop`` (resident).

Set-up builds the token stream from the seed and runs one checked job of
``1 + log_every`` steps on the measured job's seeds and feed.  It is cut
into chunks as the measured job is: step 0 alone, then one ``log_every``-step
``lax.scan`` chunk, so it compiles (and the check covers) the programs the
window runs.  Its loss at the records (steps 0 and ``log_every``), the first
gradient as the optimizer gets it and the parameters' change over the job
are what the plain reference is compared with.  (DSPG keeps no full
gradient, so it runs one more job of one step, through the compiled
one-step program, and works the first gradient out from that state.)

``train_loop`` builds its state from the configuration's seed on every
call, so the measured job retraces the checked job's first steps through
the same compiled programs; its records at steps 0 and ``log_every`` have
to equal the checked job's exactly.  The measured job is one long
``train_loop`` call with a tracker of the benchmark's own.  The trainer
pulls the loss and direction norm at each log boundary, which waits for the
device; the tracker reads the clock there.  The window opens at the first
boundary (step 0 done) and closes at the first snapshot-period boundary
after ``--seconds``, so it holds whole periods; the tracker then stops the
job.

The model is the architecture module's that the configuration names
(``"arch"``, ``bench/archs/<arch>.py``): the program's ``ModelConfig``, the
operation count, the leaf names and the plain model reference all come from
it; ``bench/reference/lm.py`` holds the training rule every architecture
shares.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import numpy as np

from bench import harness
from bench.harness import Check, Outcome, log
from bench.reference import lm as ref_lm
from bench.seeds import derive
from bench.traffic import tokens as tok

STREAM_TOKENS = 500_000         # the token stream, split over the nodes
MAX_PERIODS = 40                # snapshot periods planned for the window


@functools.lru_cache(maxsize=None)
def _l1(lam: float):
    """One prox instance per strength: the trainer finds its compiled steps
    by the prox's identity, so a new one would compile them again."""
    from repro.core import prox
    return prox.l1(lam)


class _WindowClosed(Exception):
    """Raised by the tracker to stop the measured job at the window's end."""


def flops_per_step(config: dict, job: dict) -> float:
    """Operations the algorithm as written requires per step."""
    per_token = harness.load_arch(config).train_flops_per_token(
        config["model"], job["seq_len"])
    tokens = job["nodes"] * job["per_node_batch"] * job["seq_len"]
    if job["algorithm"] == "dpsvrg":
        snap = tokens * job["snapshot_batch_mult"] / job["snapshot_every"]
        return per_token * (2 * tokens + snap)
    return per_token * tokens


@dataclasses.dataclass
class Setup:
    """What one ``--seed`` makes: the stream, the seeds, the job's pieces,
    and the architecture module the configuration names."""
    config: dict
    arch: object
    job: dict
    seeds: dict
    stream: object
    prox: object

    @classmethod
    def build(cls, config: dict, job: dict, seed: int) -> "Setup":
        arch = harness.load_arch(config)
        seeds = derive(seed, ("init", "loader", "tokens"))
        stream = tok.make_token_stream(STREAM_TOKENS,
                                       config["model"]["vocab_size"],
                                       seeds["tokens"])
        return cls(config, arch, job, seeds, stream, _l1(job["l1"]))

    @property
    def check_steps(self) -> int:
        return 1 + self.job["log_every"]

    def train(self, num_steps: int, tracker=None):
        """One ``train_loop`` job of ``num_steps`` steps on the measured
        job's seeds and feed."""
        from repro.core import graphs
        from repro.data.loader import LMLoader
        from repro.train import trainer
        job, m = self.job, self.job["nodes"]
        tc = trainer.TrainerConfig(
            num_steps=num_steps, snapshot_every=job["snapshot_every"],
            snapshot_batch_mult=job["snapshot_batch_mult"],
            alpha=job["alpha"], consensus_rounds=job["consensus_rounds"],
            algorithm=job["algorithm"], log_every=job["log_every"],
            seed=self.seeds["init"], resident=True, sampling="host")
        data = LMLoader(self.stream, num_nodes=m,
                        per_node_batch=job["per_node_batch"],
                        seq_len=job["seq_len"], seed=self.seeds["loader"])
        sched = graphs.b_connected_ring_schedule(m, b=job["schedule_b"],
                                                 seed=0)
        return trainer.train_loop(self.arch.program_config(self.config),
                                  self.prox, sched, data, tc,
                                  tracker=tracker)

    def program_answers(self) -> dict:
        """The checked job's numbers: the loss and direction norm at its
        records, the per-leaf norms of the first gradient as the optimizer
        gets it (DPSVRG: the snapshot's full gradient, which is step 0's
        direction; DSPG: (x0 - x1) / alpha) and of the parameters' change
        over the job.  The snapshot holds x0 in both: DPSVRG refreshes it at
        step 0 only, DSPG never.

        Every reference to the checked job's state is dropped before DSPG's
        one-step job starts: its trees and the new job's do not fit one
        chip together at the configuration's widths."""
        import jax
        nesting = self.arch.NESTING
        hist = self.train(self.check_steps)
        state = hist.pop("final_state")
        answers = {"step": list(hist["step"]),
                   "loss": [float(v) for v in hist["loss"]],
                   "v_norm": [float(v) for v in hist["v_norm"]]}
        answers["change"] = ref_lm.leaf_norms(jax.tree.map(
            lambda x, x0: x - x0, state.params, state.snapshot), nesting)
        if self.job["algorithm"] == "dpsvrg":
            answers["grad"] = ref_lm.leaf_norms(state.full_grad, nesting)
            return answers
        del hist, state
        one = self.train(1)["final_state"]
        alpha = self.job["alpha"]
        answers["grad"] = ref_lm.leaf_norms(jax.tree.map(
            lambda x0, x1: (x0 - x1) / alpha, one.snapshot, one.params),
            nesting)
        return answers

    def reference_answers(self, precision="highest", fault=None) -> dict:
        shards = tok.node_shards(self.stream, self.job["nodes"])
        run = ref_lm.Run(self.arch.reference, self.config["model"], self.job,
                         self.seeds, shards, precision=precision, fault=fault)
        return run.answers(self.check_steps)


def _leaf_gap(got: dict, want: dict, keep) -> float:
    """Worst leaf's gap between the two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    median = float(np.median([want[k] for k in keep]))
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in keep)


def compare(got: dict, want: dict, limits: dict) -> list:
    """The numbers a run is judged by, each beside its limit: the worst
    relative gap of the loss at the checked job's records, and the worst
    leaf's gap of the first gradient's norm and of the parameters' change.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out."""
    if set(got["grad"]) != set(want["grad"]):
        raise ValueError(f"leaves differ: program {sorted(got['grad'])}, "
                         f"reference {sorted(want['grad'])}")
    loss = max(abs(g - want["loss"][s]) / abs(want["loss"][s])
               for s, g in zip(got["step"], got["loss"]))
    median = float(np.median(list(want["grad"].values())))
    keep = [k for k, v in want["grad"].items() if v >= 1e-3 * median]
    return [Check("loss", float(loss), limits["loss"]),
            Check("grad", _leaf_gap(got["grad"], want["grad"], keep),
                  limits["grad"]),
            Check("change", _leaf_gap(got["change"], want["change"], keep),
                  limits["change"])]


def as_program(answers: dict) -> dict:
    """A reference's answers in the program's place: its loss at the
    program's record steps."""
    steps = [s for s in range(len(answers["loss"]))
             if s == 0 or s == len(answers["loss"]) - 1]
    return dict(answers, step=steps,
                loss=[answers["loss"][s] for s in steps])


def run(cell) -> Outcome:
    from repro.train.tracker import Tracker

    job = cell.workload["job"]
    setup = Setup.build(cell.config, job, cell.seed)
    m, period = job["nodes"], job["snapshot_every"]

    log(f"checked job: {setup.check_steps} steps")
    got = setup.program_answers()

    window = cell.window
    seconds = min(cell.seconds, job["trace_seconds"]) if window.trace \
        else cell.seconds

    class WindowTracker(Tracker):
        def __init__(self):
            self.records, self.last = {}, 0

        def log_metrics(self, metrics, *, step):
            self.records[step] = (metrics["loss"], metrics["v_norm"])
            if step == 0:
                window.open()
                return
            self.last = step
            if step % period == 0 and \
                    time.perf_counter() - window.t_open >= seconds:
                window.close()
                raise _WindowClosed

    tracker = WindowTracker()
    total = 1 + period * MAX_PERIODS
    log(f"measured job: up to {total} steps")
    try:
        setup.train(total, tracker=tracker)
        window.close()          # the planned job ran out before --seconds
    except _WindowClosed:
        pass
    steps = tracker.last
    tokens = steps * m * job["per_node_batch"] * job["seq_len"]
    failed = int(sum(not math.isfinite(loss)
                     for loss, _ in tracker.records.values()))
    log(f"window: {steps} steps in {window.seconds:.3f} s")

    def check() -> list:
        want = setup.reference_answers()
        log(f"losses at steps {got['step']}: program {got['loss']!r} "
            f"reference {[want['loss'][s] for s in got['step']]!r}")
        tie = sum(abs(a - b) for s, loss, v in zip(got["step"], got["loss"],
                                                   got["v_norm"])
                  for a, b in zip(tracker.records[s], (loss, v)))
        return compare(got, want, cell.workload["limits"]) + [
            Check("window_tie", float(tie), 0.0)]

    return Outcome(
        attempted=steps, failed=failed, steps=steps,
        end_to_end={"train_tokens_per_s": tokens / window.seconds},
        counts={"flops_per_step": flops_per_step(cell.config, job)},
        check=check, release=lambda: None)
