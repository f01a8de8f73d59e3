"""Production training driver.

Config-driven front end for ``trainer.train_loop``:

  * execution path: ``--resident`` (device-resident chunked scan, the
    default) or ``--host`` (one dispatch per step); ``--sampling device``
    moves minibatch drawing into the compiled chunk body,
  * persistence: ``--ckpt-dir``/``--ckpt-every``/``--keep-last``, and
    ``--resume`` to continue bitwise from ``checkpoint.latest_step``,
  * metrics: ``--tracker jsonl:<path>`` streams one JSON line per log
    window next to the in-memory history,
  * model: the smoke variant of ``--arch`` by default (CPU-sized);
    ``--layers N`` keeps every published width and cuts only the depth.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --steps 50 --resident --ckpt-dir /tmp/run0 --ckpt-every 25 \
        --tracker jsonl:/tmp/run0/metrics.jsonl
"""

from __future__ import annotations

import argparse


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the published widths and cut the depth to "
                         "N layers (0 = the CPU-sized smoke variant)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="steps between SVRG snapshot refreshes "
                         "(0 = max(steps // 4, 10))")
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--lam", type=float, default=1e-6)
    ap.add_argument("--algorithm", default="dpsvrg",
                    choices=["dpsvrg", "dspg"])
    path = ap.add_mutually_exclusive_group()
    path.add_argument("--resident", dest="resident", action="store_true",
                      default=True,
                      help="device-resident chunked execution (default)")
    path.add_argument("--host", dest="resident", action="store_false",
                      help="per-step host loop")
    ap.add_argument("--sampling", default="host", choices=["host", "device"],
                    help="where minibatch window starts are drawn "
                         "(device = inside the compiled chunk; resident only)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=0,
                    help="prune all but the N newest checkpoints (0 = keep "
                         "everything)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from checkpoint.latest_step(ckpt_dir)")
    ap.add_argument("--tracker", default="",
                    help="extra metrics sink, e.g. jsonl:/tmp/metrics.jsonl")
    args = ap.parse_args(argv)

    from repro import configs
    from repro.core import graphs, prox
    from repro.data import loader, synthetic
    from repro.train import trainer

    cfg = configs.get_config(args.arch)
    cfg = (configs.depth_variant(cfg, args.layers) if args.layers
           else configs.smoke_variant(cfg))
    if cfg.frontend != "none":
        raise SystemExit(f"{args.arch}: use examples/serve_lm.py for "
                         "modality-stub archs, or a text arch here")
    stream = synthetic.make_token_stream(500_000, cfg.vocab_size, seed=0)
    ld = loader.LMLoader(stream.tokens, num_nodes=args.nodes,
                         per_node_batch=4, seq_len=args.seq_len)

    sched = graphs.b_connected_ring_schedule(args.nodes, b=2, seed=0)
    tc = trainer.TrainerConfig(
        num_steps=args.steps,
        snapshot_every=args.snapshot_every or max(args.steps // 4, 10),
        alpha=args.alpha, consensus_rounds=2, algorithm=args.algorithm,
        log_every=max(args.steps // 10, 1),
        ckpt_dir=args.ckpt_dir or None,
        ckpt_every=args.ckpt_every or (args.steps if args.ckpt_dir else 0),
        keep_last=args.keep_last or None,
        resident=args.resident, sampling=args.sampling,
        tracker=args.tracker or None)
    hist = trainer.train_loop(cfg, prox.l1(args.lam), sched, ld, tc,
                              resume=args.resume)
    print(f"{cfg.name}: step loss:", list(zip(
        hist["step"], [round(l, 4) for l in hist["loss"]])))
    print("transfers:", hist["transfers"])
    return hist


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
