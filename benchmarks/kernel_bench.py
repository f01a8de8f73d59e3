"""Kernel microbenchmarks.

On this CPU container the Pallas kernels run in interpret mode (correctness
only), so wall-clock timing compares the UNFUSED vs FUSED jnp expression
chains that the kernels replace, and the `derived` column reports the
roofline-predicted v5e time from the kernels' HBM traffic model:

  svrg_step : 5 streams (4 in + 1 out) x 4 B  -> bytes / 819 GB/s
  mix_prox  : 4 streams                        -> bytes / 819 GB/s
  flash fwd : (q + k + v + o) streams, no S^2 materialization

``python -m benchmarks.kernel_bench --json [PATH]`` times the fused
resident step end to end through ``runner.run(exec=ExecSpec(kernel=...))`` — paper scale
(m=8, d=30) where ``kernel="auto"`` must fall back to the unfused body
without regressing, and an LM-sized d=131072 stack where the fused path
must win — and MERGES the results as a ``"kernels"`` section into PATH
(default ``BENCH_runner.json``), preserving whatever sections runner_bench
already wrote there.  ``benchmarks.check_bench`` gates the section against
the committed baseline.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithm, dpsvrg, gossip, graphs, prox, runner
from repro.kernels.fused_update import ops as fu_ops, ref as fu_ref
from repro.core.exec_spec import ExecSpec
from . import common

HBM_BW = 819e9
LARGE_D = 131072


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run(scale: float = 0.02):
    rows = []
    rng = np.random.default_rng(0)
    rows_n = 2048  # 2048*1024*4B = 8 MiB per stream
    shp = (rows_n, 1024)
    x, gn, gs, mu = (jnp.asarray(rng.normal(size=shp), jnp.float32)
                     for _ in range(4))

    unfused = jax.jit(lambda x, gn, gs, mu: jnp.sign(
        x - 0.05 * (gn - gs + mu)) * jnp.maximum(
        jnp.abs(x - 0.05 * (gn - gs + mu)) - 0.01, 0.0))
    t_unfused = _time(unfused, x, gn, gs, mu)

    fused_ref = jax.jit(lambda x, gn, gs, mu: fu_ref.mix_prox_ref(
        fu_ref.svrg_step_ref(x, gn, gs, mu, 0.05), x, x, 1 / 3, 1 / 3, 1 / 3,
        0.01))
    t_fused = _time(fused_ref, x, gn, gs, mu)

    nbytes = int(np.prod(shp)) * 4
    pred_svrg = (5 * nbytes) / HBM_BW * 1e6
    pred_mix = (4 * nbytes) / HBM_BW * 1e6
    rows.append(common.Row("kernel/svrg_step_unfused_jnp", t_unfused,
                           f"streams=5 bytes={nbytes * 5}"))
    rows.append(common.Row("kernel/fused_chain_jnp", t_fused,
                           f"v5e_pred_us={pred_svrg + pred_mix:.1f} "
                           f"(svrg {pred_svrg:.1f} + mix_prox {pred_mix:.1f})"))

    # interpret-mode correctness spot check counts as a bench row
    q = fu_ops.svrg_step(x[:8], gn[:8], gs[:8], mu[:8], 0.05)
    err = float(jnp.max(jnp.abs(
        q - fu_ref.svrg_step_ref(x[:8], gn[:8], gs[:8], mu[:8], 0.05))))
    rows.append(common.Row("kernel/svrg_step_pallas_interpret", 0.0,
                           f"allclose_err={err:.1e}"))

    # flash attention HBM model at train_4k-ish tile
    b, h, s, hd = 1, 8, 4096, 128
    io_bytes = (b * s * h * hd * 2) * 4  # q + o, bf16=2B but f32 here
    kv_bytes = (b * s * h * hd * 2) * 4
    naive_extra = b * h * s * s * 4      # materialized scores
    rows.append(common.Row(
        "kernel/flash_attention_hbm_model", 0.0,
        f"flash_bytes={io_bytes + kv_bytes} naive_extra={naive_extra} "
        f"saving={naive_extra / (io_bytes + kv_bytes):.1f}x"))

    # fused resident step through runner.run(exec=ExecSpec(kernel=...)): the end-to-end
    # rows check_bench gates (paper scale must not regress under "auto",
    # the LM-sized stack must win under the fused path)
    ks = kernel_stats(scale)
    ps, ld = ks["paper_scale"], ks["large_d"]
    rows.append(common.Row(
        "kernel/resident_paper_scale_auto",
        ps["auto_ms_per_step"] * 1e3,
        f"d={ps['param_dim']} auto->unfused fallback, xla="
        f"{ps['xla_ms_per_step'] * 1e3:.1f}us/step bitwise="
        f"{ps['auto_matches_xla_bitwise']}"))
    rows.append(common.Row(
        "kernel/resident_large_d_pallas",
        ld["pallas_ms_per_step"] * 1e3,
        f"d={ld['param_dim']} fused speedup="
        f"{ld['speedup_pallas_vs_xla']:.1f}x vs xla "
        f"({ld['xla_ms_per_step']:.2f} ms/step), hist_diff="
        f"{ld['history_max_abs_diff']:.1e}"))
    return rows


# ---------------------------------------------------------------------------
# the machine-tracked "kernels" section (merged into BENCH_runner.json)
# ---------------------------------------------------------------------------

def _time_step_buf(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def _step_buf_stats() -> dict:
    """Buffer-level fused step vs the equivalent unfused XLA expression at
    the paper-scale and LM-sized stacked layouts, plus the interpret-mode
    kernel's max-abs-diff vs the jitted oracle (a few f32 ulps at most)."""
    out: dict = {}
    for label, (m, d) in (("paper", (8, 30)), ("large", (8, LARGE_D))):
        rng = np.random.default_rng(0)
        m_pad, d_pad, _ = fu_ops.stacked_layout(m, d)
        streams = tuple(
            jnp.asarray(np.pad(rng.normal(size=(m, d)),
                               ((0, m_pad - m), (0, d_pad - d))), jnp.float32)
            for _ in range(4))
        w = fu_ops.pad_mix_matrix(
            jnp.asarray(rng.dirichlet(np.ones(m), size=m), jnp.float32),
            m_pad)
        fused = jax.jit(functools.partial(
            fu_ops.fused_step_buf, m=m, rule="svrg", prox_kind="l1",
            impl="ref"))
        alpha, lam = 0.05, 0.01

        def unfused(w, x, gn, gs, mu):
            # the XLA default the fused step replaces: separate correction,
            # dense einsum mix, and prox passes over the stacked buffer
            z = jnp.einsum("ij,jk->ik", w[:, :m_pad], x - alpha
                           * (gn - gs + mu))
            return jnp.sign(z) * jnp.maximum(jnp.abs(z) - alpha * lam, 0.0)

        unfused = jax.jit(unfused)
        t_fused = _time_step_buf(fused, w, streams, alpha, lam)
        t_xla = _time_step_buf(unfused, w, *streams)
        interp = jax.jit(functools.partial(
            fu_ops.fused_step_buf, m=m, rule="svrg", prox_kind="l1",
            impl="interpret"))
        diff = float(jnp.max(jnp.abs(interp(w, streams, alpha, lam)
                                     - fused(w, streams, alpha, lam))))
        out[label] = {"shape": [m, d], "fused_us": t_fused,
                      "xla_us": t_xla,
                      "interpret_max_abs_diff": diff}
    return out


def _circulant8() -> "graphs.MixingSchedule":
    """Static 5-band circulant mixing matrix on 8 nodes (self 0.4, +-1 0.2,
    +-2 0.1) — a 2-hop ring whose BandedPhi/dense wire forms both lower to
    the fused kernel's mix matrix."""
    w = np.zeros((8, 8))
    for off, c in ((0, 0.4), (1, 0.2), (-1, 0.2), (2, 0.1), (-2, 0.1)):
        w[np.arange(8), (np.arange(8) + off) % 8] = \
            w[np.arange(8), (np.arange(8) + off) % 8] + c
    return graphs.static_schedule(w, name="circulant8_5band")


def kernel_stats(scale: float = 0.02) -> dict:
    """The ``"kernels"`` section: fused-vs-XLA resident ms/step at paper
    scale (m=8, d=30; ``auto`` must fall back bitwise to the unfused body)
    and at the LM-sized d=131072 stack (the fused path must win >= 1.5x
    with histories agreeing to the repo's float tolerance), plus the
    buffer-level chain timings and interpret-vs-oracle max-abs-diff."""
    from .runner_bench import _time_run

    # --- paper scale: the committed resident row's exact shape -------------
    data, flat, h, x0, d = common.setup_problem("adult_like", scale)
    sched = graphs.b_connected_ring_schedule(8, b=2, seed=0)
    problem = algorithm.Problem(common.logreg_loss, h, x0, data)
    steps = 600

    def make():
        return algorithm.dspg_algorithm(
            problem, dpsvrg.DSPGHyperParams(alpha0=0.2), num_steps=steps)

    kw = dict(record_every=100, resident=True, gossip="dense")
    t_xla = _time_run(make(), problem, sched, **kw)
    t_auto = _time_run(make(), problem, sched, kernel="auto", **kw)
    t_pallas = _time_run(make(), problem, sched, kernel="pallas", **kw)
    spec = ExecSpec(resident=True, gossip="dense")
    r_xla = runner.run(make(), problem, sched, spec, seed=0,
                       record_every=100)
    r_auto = runner.run(make(), problem, sched,
                        spec.replace(kernel="auto"), seed=0,
                        record_every=100)
    r_pallas = runner.run(make(), problem, sched,
                          spec.replace(kernel="pallas"), seed=0,
                          record_every=100)
    bitwise = bool(np.array_equal(r_xla.history.objective,
                                  r_auto.history.objective))
    pallas_diff = float(np.max(np.abs(r_xla.history.objective
                                      - r_pallas.history.objective)))
    np.testing.assert_allclose(r_pallas.history.objective,
                               r_xla.history.objective, rtol=1e-4, atol=1e-6)
    paper = {
        "algorithm": "dspg", "steps": steps, "m": 8, "param_dim": int(d),
        "schedule": "bring8_b2", "scale": scale,
        "xla_ms_per_step": t_xla / 1e3 / steps,
        "auto_ms_per_step": t_auto / 1e3 / steps,
        "pallas_ms_per_step": t_pallas / 1e3 / steps,
        "auto_matches_xla_bitwise": bitwise,
        "history_max_abs_diff": pallas_diff,
    }

    # --- LM-sized stack: loopless SVRG on the banded ring transport --------
    # The realistic large-d deployment: ring topology, banded wire format.
    # The unfused body pays one shifted pass per band for the gossip mix on
    # top of the separate SVRG-correction and prox passes; the fused step
    # lowers BandedPhi to the dense mix matrix and does the whole update in
    # one kernel.  (On an all-to-all DENSE transport XLA's einsum chunk body
    # is already well-fused and the fused path only reaches parity — the
    # banded row is where the kernel earns its keep.)
    m, dL, stepsL = 8, LARGE_D, 40
    rng = np.random.default_rng(0)
    n_i = 4
    dataL = {"features": jnp.asarray(
        rng.normal(size=(m, n_i, dL)) / np.sqrt(dL), jnp.float32),
        "labels": jnp.asarray(
            rng.integers(0, 2, size=(m, n_i)) * 2.0 - 1.0, jnp.float32)}
    x0L = gossip.stack_tree(jnp.zeros(dL), m)
    problemL = algorithm.Problem(common.logreg_loss, prox.l1(0.01), x0L,
                                 dataL)
    schedL = _circulant8()

    def makeL():
        return algorithm.loopless_dpsvrg_algorithm(
            problemL, 0.05, stepsL, consensus_rounds=1, batch_size=1)

    kwL = dict(record_every=20, resident=True, gossip="banded")
    tL_xla = _time_run(makeL(), problemL, schedL, **kwL)
    tL_pallas = _time_run(makeL(), problemL, schedL, kernel="pallas", **kwL)
    specL = ExecSpec(resident=True, gossip="banded")
    rL_xla = runner.run(makeL(), problemL, schedL, specL, seed=0,
                        record_every=20)
    rL_pallas = runner.run(makeL(), problemL, schedL,
                           specL.replace(kernel="pallas"), seed=0,
                           record_every=20)
    diffL = float(np.max(np.abs(rL_xla.history.objective
                                - rL_pallas.history.objective)))
    np.testing.assert_allclose(rL_pallas.history.objective,
                               rL_xla.history.objective,
                               rtol=1e-4, atol=1e-6)
    large = {
        "algorithm": "loopless_dpsvrg", "steps": stepsL, "m": m,
        "param_dim": dL, "schedule": schedL.name, "gossip": "banded",
        "xla_ms_per_step": tL_xla / 1e3 / stepsL,
        "pallas_ms_per_step": tL_pallas / 1e3 / stepsL,
        "speedup_pallas_vs_xla": tL_xla / tL_pallas,
        "history_max_abs_diff": diffL,
    }

    return {"paper_scale": paper, "large_d": large,
            "step_buf": _step_buf_stats()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--json", nargs="?", const="BENCH_runner.json",
                    default=None, metavar="PATH",
                    help="MERGE the fused-step stats as a 'kernels' section "
                         "into PATH (default BENCH_runner.json), keeping "
                         "runner_bench's sections intact")
    args = ap.parse_args()
    if args.json:
        out = {}
        if os.path.exists(args.json):
            with open(args.json) as f:
                out = json.load(f)
        out["kernels"] = kernel_stats(args.scale)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        ks = out["kernels"]
        print(f"wrote {args.json} (kernels section)")
        ps, ld = ks["paper_scale"], ks["large_d"]
        print(f"  paper(d={ps['param_dim']})  xla="
              f"{ps['xla_ms_per_step']:.4f} auto="
              f"{ps['auto_ms_per_step']:.4f} ms/step "
              f"bitwise_fallback={ps['auto_matches_xla_bitwise']}")
        print(f"  large(d={ld['param_dim']}) xla="
              f"{ld['xla_ms_per_step']:.3f} pallas="
              f"{ld['pallas_ms_per_step']:.3f} ms/step "
              f"({ld['speedup_pallas_vs_xla']:.1f}x, hist_diff="
              f"{ld['history_max_abs_diff']:.1e})")
    else:
        print("name,us_per_call,derived")
        for r in run(args.scale):
            print(f"{r.name},{r.us_per_call:.1f},{r.derived}")


if __name__ == "__main__":
    main()
