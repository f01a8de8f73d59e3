"""One run of one benchmark cell, driven by the data under ``bench/``.

Everything that belongs to one configuration, one workload or one
per-layer metric is a file of its own, found by name:

* ``bench/workloads/<cell>.json`` names its configuration, chips, driver,
  job parameters and the limits of its correctness check;
* ``bench/configs/<config>.json`` holds the configuration as it is run;
* ``bench/drivers/<driver>.py`` drives one entry point of the program;
* ``bench/archs/<arch>.py`` is one LM architecture, named by the key
  ``"arch"`` of an ``lm_train`` configuration.  It exports
  ``program_config(config)``, the program's ``repro.models.api.ModelConfig``;
  ``train_flops_per_token(model, seq)``, the operations of one forward and
  backward pass per token; ``NESTING``, the keys of the program's parameter
  tree that the reference's does not have; ``TINY``, the model sizes of a
  run on the CPU; and ``reference``, the plain model module that
  ``bench/reference/lm.py``'s training rule trains (its docstring says what
  that module exports);
* ``bench/metrics/<metric>.py`` reads one per-layer metric from the
  reduced trace and the run's counts.

A run: refuse unless the platform is a TPU with the chips the cell needs;
turn on the persistent compile cache at a fixed path in the checkout; let
the driver build data and weights from the seed, warm up the cell's own
shapes and measure its window (profiled with ``--trace 1``); read the
device's peak memory; release the program's state; run the driver's
comparison with the plain reference; print one JSON line.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
import traceback
from typing import Any, Callable

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The platform or the chip count does not match the cell."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the data, found by name
# ---------------------------------------------------------------------------

def _json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_workload(name: str) -> dict:
    wl = _json(BENCH / "workloads" / f"{name}.json")
    wl["name"] = name
    return wl


def load_config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def load_arch(config: dict):
    """The architecture module that an LM configuration names under its key
    ``"arch"``."""
    name = config.get("arch")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"architecture: its key \"arch\" holds {name!r}")
    if importlib.util.find_spec(f"bench.archs.{name}") is None:
        raise ValueError(f"configuration {config.get('name')!r}: its key "
                         f"\"arch\" names {name!r}, and there is no "
                         f"bench/archs/{name}.py")
    return importlib.import_module(f"bench.archs.{name}")


def load_reader(metric: str) -> Callable[[dict], float | None]:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if kind not in table["chips"]:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json; have {sorted(table['chips'])}")
    return table["chips"][kind]


def benchmark_entry(name: str) -> dict:
    """The cell's entry in BENCHMARK.json, with the metrics it reports."""
    spec = _json(ROOT / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"{name!r} is not a cell of BENCHMARK.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


# ---------------------------------------------------------------------------
# what a driver is handed, and what it hands back
# ---------------------------------------------------------------------------

class Window:
    """The measured window's clock, compile count and profiler.

    The driver calls ``open()`` where its first measured step begins and
    ``close()`` where its last one ends; with ``trace`` set the profiler
    records exactly that span, under a host span named ``bench.window``."""

    def __init__(self, trace: bool, trace_dir: pathlib.Path,
                 compiles: "CompileCounter"):
        self.trace, self.trace_dir, self.compiles = trace, trace_dir, compiles
        self.t_open = self.t_close = None
        self._c0 = 0
        self._annotation = None

    def open(self) -> None:
        import jax
        self.t_open = time.perf_counter()
        self._c0 = self.compiles.count
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()

    def close(self) -> None:
        import jax
        self.t_close = time.perf_counter()
        if self.trace:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.compiles_inside = self.compiles.count - self._c0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class CompileCounter:
    """Counts backend compilations through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    seed: int
    seconds: float
    window: Window


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    steps: int                      # steps completed inside the window
    end_to_end: dict                # metric name -> value (setup_s apart)
    counts: dict                    # per-step work counts for the readers
    check: Callable[[], list]       # runs the reference; -> [Check]
    release: Callable[[], None]     # drops the program's state


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chips(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r}; nothing "
                     f"is measured off the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def per_layer(entry: dict, ctx: dict) -> dict:
    out = {}
    for metric in entry["per_layer"]:
        value = load_reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """One run of cell ``name``; returns the result line as a dict."""
    workload = load_workload(name)
    return run_loaded(benchmark_entry(name), workload,
                      load_config(workload["config"]), seed, seconds, trace,
                      t0)


def run_loaded(entry: dict, workload: dict, config: dict, seed: int,
               seconds: float, trace: bool, t0: float) -> dict:
    """A run from the cell's loaded data (tests hand in small sizes)."""
    name = workload["name"]
    chips = entry["cell"]["chips"]
    devices = require_chips(chips)[:chips]
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    window = Window(trace, TRACE_DIR / name, compiles)
    cell = Cell(workload=workload, config=config, seed=seed,
                seconds=seconds, window=window)
    outcome = load_driver(workload["driver"]).run(cell)
    setup_s = window.t_open - t0
    log(f"compiles inside the window: {window.compiles_inside} "
        f"({compiles.count} in the run, {compiles.seconds:.3f} s)")
    memory = peak_bytes(devices)
    outcome.release()
    gc.collect()

    result: dict[str, Any] = {}
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    if trace:
        from bench import scopes
        from bench import trace as trace_lib
        summary = trace_lib.reduce_dir(window.trace_dir, len(devices))
        named = scopes.reduce(scopes.events(scopes.newest(window.trace_dir)))
        shutil.rmtree(window.trace_dir, ignore_errors=True)
        ctx = {"workload": workload, "steps": outcome.steps,
               "window_s": window.seconds, "trace": summary,
               "scopes": scopes.per_step(named, outcome.steps),
               "counts": outcome.counts, "chips": len(devices),
               "peaks": load_peaks(kind)}
        metrics = per_layer(entry, ctx)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
    else:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in entry["end_to_end"]}
    checks = outcome.check()
    correct = bool(checks) and all(c.ok for c in checks) \
        and outcome.failed == 0
    line = {"compiles_in_window": window.compiles_inside,
            "correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    line.update(result)
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return line


def main(args, t0: float) -> int:
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t0)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    print(f"compiles_in_window={line.pop('compiles_in_window')}",
          flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
