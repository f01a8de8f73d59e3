"""Benchmark entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--scale`` shrinks the Table-I
dataset sizes (default 0.02 keeps the full suite CPU-friendly; the
qualitative paper claims being validated are scale-free)."""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig1,fig2,fig3,fig4,fig5,"
                         "table1,kernel")
    ap.add_argument("--resident", action="store_true",
                    help="drive the fig sweeps through the device-resident "
                         "runner path (one transfer per run; histories "
                         "agree with the host path to float tolerance)")
    ap.add_argument("--sweep-batched", action="store_true",
                    help="stage each fig experiment grid (λ / connectivity "
                         "/ seeds) as ONE batched resident device program "
                         "via runner.run_sweep — O(1) transfers per fig, "
                         "identical schedules across cells")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from . import (baselines_compare, beyond_noniid, datasets_table,
                   fig1_convergence, fig2_comm, fig3_consensus, fig4_lambda,
                   fig5_connectivity, kernel_bench, runner_bench)
    suites = {
        "table1": datasets_table.run,
        "fig1": fig1_convergence.run,
        "fig2": fig2_comm.run,
        "fig3": fig3_consensus.run,
        "fig4": fig4_lambda.run,
        "fig5": fig5_connectivity.run,
        "kernel": kernel_bench.run,
        "runner": runner_bench.run,
        "beyond": beyond_noniid.run,
        "baselines": baselines_compare.run,
    }
    only = {s for s in args.only.split(",") if s}
    # the fig sweeps accept resident=; the non-sweep suites don't; the
    # grid-shaped figs additionally batch their whole grid into one
    # resident device program under --sweep-batched
    resident_aware = {"fig1", "fig2", "fig3", "fig4", "fig5"}
    sweep_aware = {"fig1", "fig4", "fig5"}
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            kw = {}
            if args.resident and name in resident_aware:
                kw["resident"] = True
            if args.sweep_batched and name in sweep_aware:
                kw["sweep_batched"] = True
            rows = fn(args.scale, **kw)
        except Exception as e:  # pragma: no cover
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            raise
        for r in rows:
            print(f"{r.name},{r.us_per_call:.1f},{r.derived}")
        print(f"{name}/total_wall_s,{(time.time() - t0) * 1e6:.0f},",
              file=sys.stderr)


if __name__ == "__main__":
    main()
