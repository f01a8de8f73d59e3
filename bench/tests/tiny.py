"""Small cells for runs on the CPU: the real cells' data with every size
cut so that a run takes seconds.  Only the tests use them."""

from __future__ import annotations

import copy
import json
import time

from bench import harness

LM_JOB = {"nodes": 2, "seq_len": 32, "per_node_batch": 2,
          "snapshot_every": 8, "snapshot_batch_mult": 2, "log_every": 4,
          "alpha": 0.05, "trace_seconds": 0.5}
# the paper job keeps its full length (about 760 steps), over which the
# control's rounding grows to where the check can see it
PAPER_PROBLEM = {"rows": 2400, "features": 64, "teacher_active": 4}
PAPER_JOB = {"trace_seconds": 0.5}


def _entry(name: str, driver: str) -> dict:
    """The cell's entry in BENCHMARK.json; for a workload file that is not
    a cell yet, the entry of the first cell with the same driver."""
    try:
        return harness.benchmark_entry(name)
    except KeyError:
        spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        like = next(w["name"] for w in spec["workloads"]
                    if harness.load_workload(w["name"])["driver"] == driver)
        return harness.benchmark_entry(like)


def cell(name: str, nodes: int | None = None):
    """(entry, workload, config) of cell ``name`` at a small size."""
    workload = copy.deepcopy(harness.load_workload(name))
    entry = _entry(name, workload["driver"])
    config = copy.deepcopy(harness.load_config(workload["config"]))
    if workload["driver"] == "lm_train":
        config["model"].update(harness.load_arch(config).TINY)
        workload["job"].update(LM_JOB)
    else:
        config["problem"].update(PAPER_PROBLEM)
        workload["job"].update(PAPER_JOB)
        if nodes is not None:
            config["nodes"] = nodes
    entry = dict(entry, cell=dict(entry["cell"], chips=1))
    return entry, workload, config


def run(name: str, seed: int = 7, *, seconds: float = 0.3,
        nodes: int | None = None) -> dict:
    """One run of the small cell on the CPU, with the look for a chip and
    the persistent compile cache stubbed out."""
    import jax
    entry, workload, config = cell(name, nodes)
    look, cache = harness.require_chips, harness.enable_compile_cache
    harness.require_chips = lambda chips: jax.devices()
    harness.enable_compile_cache = lambda: "off"
    try:
        return harness.run_loaded(entry, workload, config, seed, seconds,
                                  False, time.perf_counter())
    finally:
        harness.require_chips, harness.enable_compile_cache = look, cache
