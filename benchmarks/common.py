"""Shared benchmark harness for the paper-reproduction figures.

Each figure module exposes ``run(scale) -> list[Row]``; ``benchmarks.run``
aggregates and prints the ``name,us_per_call,derived`` CSV.  ``scale``
shrinks the Table-I dataset sizes so the full suite completes on CPU in
minutes (paper qualitative claims are scale-free: rate ORDERS and
stability, not absolute wall time).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner, sweep
from repro.core.exec_spec import ExecSpec
from repro.data import synthetic


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str


def logreg_loss(w, batch):
    """Paper Eq. 26.  The dot runs at HIGHEST precision: at the default, a
    TPU computes an f32 ``features @ w`` in one bf16 pass."""
    logits = jnp.dot(batch["features"], w,
                     precision=jax.lax.Precision.HIGHEST)
    y = batch["labels"]
    return jnp.mean(-y * logits + jnp.log1p(jnp.exp(logits)))


def setup_problem(dataset: str, scale: float, m: int = 8, lam: float = 0.01,
                  seed: int = 0):
    ds = synthetic.make_paper_dataset(dataset, scale=scale, seed=seed)
    data = synthetic.partition_per_node(ds, m, seed=seed)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    flat = {k: v.reshape(-1, *v.shape[2:]) for k, v in data.items()}
    h = prox.l1(lam)
    d = ds.dim
    x0 = gossip.stack_tree(jnp.zeros(d), m)
    return data, flat, h, x0, d


def make_problem(data, h, x0, objective_fn=None) -> algorithm.Problem:
    return algorithm.Problem(logreg_loss, h, x0, data, objective_fn)


def run_algorithm(name: str, problem, sched, *factory_args, seed=0,
                  record_every=1, scan=False, resident=False,
                  sampling="host", gossip="dense",
                  **factory_kw) -> runner.RunResult:
    """Build ``ALGORITHMS[name]`` and drive it through ``runner.run`` — the
    one calling convention every figure script shares.  ``gossip`` pins the
    dense wire format by default so figure numbers stay comparable across
    transport-selection changes; pass "auto" or a backend name to override.
    ``resident=True`` runs device-resident (one transfer per run; histories
    agree with the host path to float tolerance with host sampling), which
    is what ``benchmarks.run --resident`` passes to every sweep."""
    algo = algorithm.ALGORITHMS[name](problem, *factory_args, **factory_kw)
    return runner.run(algo, problem, sched,
                      ExecSpec(scan=scan, resident=resident,
                               sampling=sampling, gossip=gossip),
                      seed=seed, record_every=record_every)


def run_sweep(build, grid, sched=None, *, seed=0, record_every=1,
              resident=False, sweep_batched=False, mode="product",
              gossip="dense") -> sweep.SweepResult:
    """Drive a fig-experiment grid through ``core.sweep.run_sweep`` — the
    one sweep calling convention the figure scripts share.  Default
    (``resident=False, sweep_batched=False``) runs the cells sequentially
    through the host path, reproducing the pre-sweep per-cell
    ``runner.run`` numbers exactly; ``resident=True`` runs sequential
    resident cells; ``sweep_batched=True`` stages the WHOLE grid as one
    batched device program (O(1) transfers for the entire fig sweep).
    ``gossip`` pins dense like :func:`run_algorithm`, keeping figure
    numbers comparable across transport-selection changes."""
    return sweep.run_sweep(
        build, grid, sched,
        ExecSpec(resident=resident or sweep_batched, gossip=gossip),
        seed=seed, record_every=record_every, batched=sweep_batched,
        mode=mode)


def f_star(flat, h, d, alpha=0.4, steps=4000):
    _, hist = dpsvrg.centralized_prox_gd(logreg_loss, h, jnp.zeros(d), flat,
                                         alpha, steps)
    return float(np.min(hist))


def timed(fn):
    t0 = time.time()
    out = fn()
    return out, (time.time() - t0) * 1e6
