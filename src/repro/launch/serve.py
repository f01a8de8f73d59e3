"""Production serving driver: checkpoint -> consensus params -> engine.

Config-driven front end closing the ``train -> checkpoint -> serve`` loop
(smoke-scale runnable on CPU; the FULL configs lower on the production
mesh via repro.launch.dryrun):

  * params: ``--ckpt-dir`` loads a ``launch.train`` checkpoint through
    :func:`repro.serve.consensus.consensus_params` (the node-averaged x̄,
    with per-node disagreement printed), otherwise random init,
  * engine: ``--engine resident`` (device-resident chunked decode, the
    default) or ``--engine host`` (the per-token ``ContinuousBatcher``
    loop); ``--slots``/``--max-len``/``--chunk`` size the shared cache,
  * traffic: ``--stream`` replays a seeded synthetic workload
    (``repro.serve.stream``) against the wall clock and reports
    TTFT/TPOT percentiles + sustained tokens/s; without it, one fixed
    batch of prompts is served closed-loop,
  * prefill and decode are jitted and WARMED before any timing, so
    reported ms excludes compile,
  * model: the smoke variant of ``--arch`` by default (CPU-sized);
    ``--layers N`` keeps every published width and cuts only the depth,
    ``--flash`` runs prefill attention through the Pallas flash kernel,
  * ``--verify-host`` replays the closed-loop batch through the host
    ``ContinuousBatcher`` with einsum attention and fails unless every
    output token matches.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --steps 50 --ckpt-dir /tmp/run0
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --ckpt-dir /tmp/run0 --stream --requests 32 --slots 4
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def _build_params(args, cfg):
    from repro.models import transformer
    from repro.serve import consensus

    if args.ckpt_dir:
        params, info = consensus.consensus_params(args.ckpt_dir, cfg)
        print(info)
        return params
    return transformer.init_params(cfg, jax.random.PRNGKey(args.seed))


def _build_backend(args, cfg, params):
    from repro.serve.engine import ResidentEngine
    from repro.serve.scheduler import ContinuousBatcher
    from repro.serve.stream import HostBatcherDriver

    if args.engine == "resident":
        return ResidentEngine(cfg, params, max_slots=args.slots,
                              max_len=args.max_len, chunk=args.chunk)
    return HostBatcherDriver(ContinuousBatcher(
        cfg, params, max_slots=args.slots, max_len=args.max_len))


def _warm(args, cfg, params, prompt_lens):
    """Compile prefill + decode/chunk executables before any timing."""
    from repro.serve.scheduler import Request

    t0 = time.perf_counter()
    warm = _build_backend(args, cfg, params)
    rng = np.random.default_rng(0)
    for i, plen in enumerate(sorted(set(int(p) for p in prompt_lens))):
        warm.submit(Request(uid=-1 - i, tokens=rng.integers(
            0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=2))
    while warm.busy:
        warm.step()
    return time.perf_counter() - t0


def main(argv=None):
    from repro import configs
    from repro.serve import metrics as metrics_lib
    from repro.serve import stream as stream_lib
    from repro.serve.scheduler import Request

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the published widths and cut the depth to "
                         "N layers (0 = the CPU-sized smoke variant)")
    ap.add_argument("--flash", action="store_true",
                    help="prefill attention through the flash kernel")
    ap.add_argument("--verify-host", action="store_true",
                    help="closed loop only: check every output against the "
                         "host ContinuousBatcher with einsum attention")
    ap.add_argument("--ckpt-dir", default="",
                    help="load consensus params from a launch.train "
                         "checkpoint instead of random init")
    ap.add_argument("--engine", default="resident",
                    choices=["resident", "host"])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per compiled dispatch (resident)")
    ap.add_argument("--stream", action="store_true",
                    help="replay a seeded synthetic arrival stream instead "
                         "of one fixed batch")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=64.0,
                    help="stream mean arrivals/s")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty", "batch"])
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.get_config(args.arch)
    cfg = (configs.depth_variant(cfg, args.layers) if args.layers
           else configs.smoke_variant(cfg))
    if args.flash:
        cfg = cfg.scaled(use_flash=True)
    if cfg.frontend != "none":
        raise SystemExit(f"{args.arch}: serve drives the token path; pick "
                         "a text arch (modality stubs: examples/serve_lm.py)")
    params = _build_params(args, cfg)

    if args.stream:
        sc = stream_lib.StreamConfig(
            num_requests=args.requests, vocab_size=cfg.vocab_size,
            arrival=args.arrival, rate=args.rate,
            prompt_lens=(args.prompt_len // 2 or 1, args.prompt_len),
            new_low=max(args.new // 2, 1), new_high=args.new,
            seed=args.seed)
        requests = stream_lib.make_requests(sc)
        t_warm = _warm(args, cfg, params, sc.prompt_lens)
        backend = _build_backend(args, cfg, params)
        timings = stream_lib.replay(backend, requests)
        summary = metrics_lib.summarize(timings)
        print(f"arch={cfg.name} engine={args.engine} "
              f"slots={args.slots} stream={args.arrival}@{args.rate}/s "
              f"(warmup {t_warm*1e3:.0f} ms, untimed)")
        print(f"  {summary['requests']} requests, {summary['tokens']} "
              f"tokens in {summary['span_s']*1e3:.1f} ms: "
              f"{summary['tokens_per_s']:.1f} tok/s "
              f"({summary['ms_per_token']:.3f} ms/tok)")
        for k in ("ttft_ms", "tpot_ms"):
            p = summary[k]
            print(f"  {k:8s} p50 {p['p50']:8.2f}  p95 {p['p95']:8.2f}  "
                  f"p99 {p['p99']:8.2f}")
        return summary

    # fixed closed-loop batch: submit everything at t=0, drain
    t_warm = _warm(args, cfg, params, [args.prompt_len])
    backend = _build_backend(args, cfg, params)
    rng = np.random.default_rng(args.seed)
    requests = [Request(uid=uid, tokens=rng.integers(
        0, cfg.vocab_size, size=args.prompt_len).astype(np.int32),
        max_new_tokens=args.new) for uid in range(args.requests)]
    for req in requests:
        backend.submit(req)
    t0 = time.perf_counter()
    while backend.busy:
        backend.step()
    span = time.perf_counter() - t0
    total = sum(len(v) for v in backend.outputs.values())
    print(f"arch={cfg.name} engine={args.engine} "
          f"slots={args.slots}: {args.requests} requests, {total} tokens "
          f"in {span*1e3:.1f} ms (warmup {t_warm*1e3:.0f} ms, untimed)")
    print(f"  {total/span:.1f} tok/s ({span*1e3/total:.3f} ms/tok)")
    sample = backend.outputs[0]
    print("sample:", np.asarray(sample)[:16].tolist())
    if args.verify_host:
        from repro.serve.scheduler import ContinuousBatcher
        # einsum attention on the host side, so --flash is checked too
        host = ContinuousBatcher(cfg.scaled(use_flash=False), params,
                                 max_slots=args.slots, max_len=args.max_len)
        for req in requests:
            host.submit(req)
        expect = host.run_until_done()
        bad = [u for u in expect
               if not np.array_equal(expect[u], backend.outputs[u])]
        if bad:
            raise SystemExit(f"outputs of requests {bad} differ from the "
                             f"host ContinuousBatcher")
        print(f"  all {len(expect)} outputs equal the host batcher's")
    return {"requests": args.requests, "tokens": total, "span_s": span,
            "tokens_per_s": total / span,
            "ms_per_token": span * 1e3 / total,
            "outputs": dict(backend.outputs)}


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
