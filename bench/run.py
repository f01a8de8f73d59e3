"""Run one cell of the benchmark and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations and metrics are listed in BENCHMARK.json
at the root of the checkout; see bench/harness.py for what a run does.
Without a TPU, or with fewer chips than the cell needs, the run exits
non-zero before it builds anything and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the program (src/repro) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 2
    # the script's own directory would shadow modules such as ``trace``
    sys.path[:] = [p for p in sys.path
                   if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
    for path in (str(ROOT), str(ROOT / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness
    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
