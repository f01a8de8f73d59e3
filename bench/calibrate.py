"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --others 3

In one process, on the chip: the program's readings of the compared numbers
on ``--seeds`` seeds (the lower readings), the control's on the first
``--others`` of them (the plain reference computed one precision below the
configuration's, put in the program's place), and each planted fault's
(the reference with the fault, in the program's place).  Every reading is
the same comparison with the full-precision reference that a run makes.
The benchmark's own runs never run this.  Prints one JSON object per
reading and a summary last; writes them to ``--out`` as well.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

CONTROL = {"lm_train": "bfloat16", "paper_runner": "high"}
FAULTS = ("unchanged", "half_batch", "no_mix", "answer")


def readings(name: str, seeds, others: int, faults=FAULTS, *,
             config=None, workload=None, emit=print):
    """-> list of {"kind", "seed", "values"} for cell ``name``."""
    from bench import harness
    workload = workload or harness.load_workload(name)
    config = config or harness.load_config(workload["config"])
    driver = workload["driver"]
    limits = {k: float("inf") for k in workload["limits"]}
    out = []

    def add(kind, seed, checks):
        row = {"kind": kind, "seed": seed,
               "values": {c.name: c.value for c in checks}}
        out.append(row)
        emit(json.dumps(row))

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if driver == "lm_train":
            from bench.drivers import lm_train as drv
            setup = drv.Setup.build(config, workload["job"], seed)
            want = setup.reference_answers()
            add("program", seed, drv.compare(setup.program_answers(), want,
                                             limits))
            if i < others:
                for kind, kw in [("control", {"precision": CONTROL[driver]})] \
                        + [(f"fault:{f}", {"fault": f}) for f in faults]:
                    add(kind, seed, drv.compare(drv.as_program(
                        setup.reference_answers(**kw)), want, limits))
        else:
            from bench.drivers import paper_runner as drv
            setup = drv.Setup.build(config, workload["job"], seed)
            job_seed = setup.seeds["warm"]
            want = setup.reference_job(job_seed)
            got = drv.as_answer(setup.program_job(job_seed))
            add("program", seed, drv.compare(got, want, limits))
            if i < others:
                for kind, kw in [("control", {"precision": CONTROL[driver]})] \
                        + [(f"fault:{f}", {"fault": f}) for f in faults]:
                    add(kind, seed, drv.compare(
                        setup.reference_job(job_seed, **kw), want, limits))
        harness.log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
    return out


def summary(rows) -> dict:
    """Per number: the largest program reading, the smallest control
    reading, and the smallest reading of each fault."""
    out: dict = {}
    for row in rows:
        for name, value in row["values"].items():
            entry = out.setdefault(name, {})
            if row["kind"] == "program":
                entry["lower"] = max(entry.get("lower", 0.0), value)
            else:
                entry[row["kind"]] = min(entry.get(row["kind"],
                                                   float("inf")), value)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path
                   if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    workload = harness.load_workload(args.workload)
    harness.require_chips(harness.benchmark_entry(
        args.workload)["cell"]["chips"])
    harness.log(f"compile cache: {harness.enable_compile_cache()}")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = readings(args.workload, seeds, args.others, workload=workload)
    result = {"workload": args.workload, "summary": summary(rows),
              "rows": rows}
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(result, indent=1))
    print(json.dumps({"summary": result["summary"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
