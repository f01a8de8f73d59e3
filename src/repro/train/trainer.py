"""Decentralized LM training on the resident execution engine.

``train_loop`` drives ``build_train_step`` with the paper's outer/inner
structure — snapshot (large-batch full-gradient refresh) every
``snapshot_every`` steps, multi-consensus gossip matrices from a
time-varying schedule — through two execution paths that share every
jitted kernel:

* **host loop** (default): one device dispatch per inner step, the
  reference semantics.  Accepts either an :class:`~repro.data.loader.
  LMLoader` or any legacy batch iterator.
* **resident** (``resident=True``, LMLoader only): the run is planned on
  host like ``runner.run(resident=True)`` — chunk schedule cut at
  log/checkpoint boundaries, per-step window starts, phi pytrees and
  alphas staged in ONE ``jax.device_put`` next to the stacked token-shard
  buffer — then executed through donated compiled ``lax.scan`` chunks
  whose body gathers minibatches from the resident shard buffer and folds
  the snapshot refresh in via ``lax.cond`` on precomputed per-step flags
  (the ``device_transitions`` contract).  Per-step metrics ride the scan
  ys and are pulled once per log window — O(1) host<->device transfers
  per window (``hist["transfers"]`` reports the ledger).
  ``sampling="host"`` (default) draws window starts from the loader's
  ``np.random`` stream, so host and resident histories agree to float
  tolerance; ``sampling="device"`` threads a ``jax.random`` key through
  the scan carry and draws starts inside the compiled body — zero batch
  staging, a different (seed-reproducible) stream.

Stateful gossip transports (``compressed`` error feedback, scenario
wrappers) work on both paths: the transport state lives in
``TrainState.mix_state`` and the step routes its mix through
``compression.mix_with_state``.

Metrics go to pluggable :class:`~repro.train.tracker.Tracker` sinks
(``tracker=`` accepts instances, ``"jsonl:<path>"`` specs, or lists); the
returned ``hist`` dict is the built-in ``HistoryTracker``'s view plus
``final_state`` and the transfer ledger.  Periodic + final checkpoints
(``ckpt_dir``/``ckpt_every``/``keep_last``) capture the FULL train state
(params, snapshot, full gradient, mix state, device rng key) plus the
loader's data cursor, and ``train_loop(..., resume=True)`` restores from
``checkpoint.latest_step`` with a bitwise continuation guarantee: the
resumed trajectory is step-for-step identical to the uninterrupted run on
both execution paths (same TrainerConfig required; schedules that depend
on ``num_steps`` — wsd/cosine — need the same total).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_lib
from repro.core import algorithm as algo_lib, \
    exec_spec as exec_spec_lib, graphs, prox as prox_lib, \
    runner as runner_lib, schedules, spans, sweep as sweep_lib, transport
from repro.core.exec_spec import UNSET, ExecSpec
from repro.data import loader as loader_lib
from repro.models.api import ModelConfig
from . import steps as steps_lib
from .tracker import CompositeTracker, HistoryTracker, resolve_tracker

__all__ = ["TrainerConfig", "train_loop", "train_sweep"]


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 200
    snapshot_every: int = 50        # production K (fixed; paper's K_s noted in DESIGN)
    snapshot_batch_mult: int = 4    # "full" gradient ~ mult x minibatch (loader paths)
    alpha: float = 0.05
    consensus_rounds: int = 2       # capped multi-consensus
    algorithm: str = "dpsvrg"       # core.algorithm.UPDATE_RULES name (or an UpdateRule)
    gossip: str = "auto"            # transport.GOSSIP_BACKENDS name / instance / "auto"
    lr_schedule: str = "constant"   # constant | wsd | cosine
    log_every: int = 10
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    keep_last: int | None = None    # retention: prune all but the N newest ckpts
    seed: int = 0
    resident: bool = False          # device-resident execution (LMLoader data)
    sampling: str = "host"          # "host" | "device" (resident only)
    tracker: Any = None             # tracker spec (see tracker.resolve_tracker)


def _lr_fn(tc: TrainerConfig):
    if tc.lr_schedule == "wsd":
        return schedules.wsd(tc.alpha, warmup=max(tc.num_steps // 20, 1),
                             stable=int(tc.num_steps * 0.75),
                             decay=max(tc.num_steps // 5, 1))
    if tc.lr_schedule == "cosine":
        return schedules.warmup_cosine(tc.alpha, max(tc.num_steps // 20, 1),
                                       tc.num_steps)
    return schedules.constant(tc.alpha)


def _realized_alpha_fn(tc: TrainerConfig, rule):
    """The step size the update ACTUALLY uses (recorded in metrics).

    VR-type rules (snapshot-corrected) take the configured LR schedule;
    plain stochastic rules need the DSPG decaying step to converge — a
    configured non-constant schedule would be silently ignored, so warn
    loudly instead."""
    if rule.needs_snapshot:
        return _lr_fn(tc)
    if tc.lr_schedule != "constant":
        warnings.warn(
            f"TrainerConfig.lr_schedule={tc.lr_schedule!r} is OVERRIDDEN for "
            f"the non-variance-reduced {rule.name!r} rule, which requires "
            f"the decaying DSPG step alpha0/(k+1)^0.5 to converge; the "
            f"realized step size is recorded in the 'alpha' metric column",
            RuntimeWarning, stacklevel=3)
    return schedules.dspg_stepsize(tc.alpha)


def _to_device_floats(phi):
    """Stage a wire representation, canonicalizing float leaves to f32 but
    KEEPING integer payload dtypes (quantized transports)."""
    def leaf(a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.floating):
            a = a.astype(np.float32, copy=False)
        return jnp.asarray(a)

    return jax.tree.map(leaf, phi)


class _LMChunk(NamedTuple):
    xs: Any                 # stacked per-step host xs for this chunk
    length: int             # real steps (no padding — lengths are bucketed
    #                         by the log/ckpt cadence itself)
    last_step: int          # absolute index of the chunk's final step
    record: bool            # pull ys and log after this chunk
    ckpt_next: int | None   # checkpoint step number to save, or None
    alpha_last: float       # realized alpha at last_step
    wire_end: int           # cumulative wire bytes after this chunk
    slot_end: int           # gossip slot cursor after this chunk
    loader_state: Any       # loader cursor snapshot AT this boundary (ckpt
    #                         chunks only; planning consumes the rng for the
    #                         whole run, so the live end-of-run state_dict
    #                         would be wrong for mid-run resumes)


def _make_lm_exec(bundle, *, vr: bool, sampling: str, seq_len: int,
                  batch: int, snap_batch: int):
    """Compiled chunk executor for the resident LM path: donated TrainState
    carry, in-scan window gathers from the resident (m, shard_len) token
    buffer, snapshot refreshes under ``lax.cond`` on the precomputed
    per-step flags, per-step (loss, v_norm) metrics riding the scan ys.
    Cached on the bundle's step identities via the runner's persistent
    executor cache, so rebuilt ``train_loop`` calls over the same model
    recompile nothing."""
    train_step = bundle.train_step
    snapshot_step = bundle.snapshot_step
    device_sampling = sampling == "device"

    def make():
        L = seq_len

        def gather(shards, starts):
            with spans.scope(spans.SAMPLE):
                win = jax.vmap(
                    lambda row, st: row[st[:, None]
                                        + jnp.arange(L + 1)[None, :]])(
                                            shards, starts)
                return {"tokens": win[..., :L], "labels": win[..., 1:]}

        @functools.partial(jax.jit, donate_argnums=0)
        def exec_chunk(carry, xs, shards):
            m = shards.shape[0]
            hi = shards.shape[1] - L - 1

            def body(carry, xs):
                if device_sampling:
                    state, key = carry
                    if vr:
                        snap, phi, alpha = xs
                        key, k1, k2 = jax.random.split(key, 3)
                    else:
                        phi, alpha = xs
                        key, k1 = jax.random.split(key)
                    starts = jax.random.randint(k1, (m, batch), 0, hi)
                    if vr:
                        def do_snap(s):
                            sstarts = jax.random.randint(
                                k2, (m, snap_batch), 0, hi)
                            return snapshot_step(s, gather(shards, sstarts))

                        with spans.scope(spans.SNAPSHOT):
                            state = jax.lax.cond(snap, do_snap, lambda s: s,
                                                 state)
                else:
                    state = carry
                    if vr:
                        starts, sstarts, snap, phi, alpha = xs
                        with spans.scope(spans.SNAPSHOT):
                            state = jax.lax.cond(
                                snap,
                                lambda s: snapshot_step(
                                    s, gather(shards, sstarts)),
                                lambda s: s, state)
                    else:
                        starts, phi, alpha = xs
                state, mets = train_step(state, gather(shards, starts), phi,
                                         alpha)
                out = (state, key) if device_sampling else state
                return out, (mets["loss"], mets["v_norm"])

            return jax.lax.scan(body, carry, xs)

        return exec_chunk

    return runner_lib._shared_exec(
        ("lm_resident", train_step, snapshot_step, vr, sampling, seq_len,
         batch, snap_batch), make)


def _check_lm_spec(spec: ExecSpec, caller: str) -> None:
    """The LM trainer consumes the SAME ExecSpec as ``runner.run`` but only
    implements the host-loop / resident halves of it; fields that select
    repro-scale-only machinery fail loudly instead of being ignored."""
    if spec.scan:
        raise ValueError(f"{caller}: the LM trainer has no scan path — "
                         f"ExecSpec(scan=True) selects runner.run's "
                         f"lax.scan fast path; use resident=True here")
    if spec.kernel != "xla":
        raise ValueError(f"{caller}: ExecSpec(kernel={spec.kernel!r}) "
                         f"selects the repro-scale fused resident step; the "
                         f"LM trainer's kernels come from the model config")
    if spec.device_transitions is False:
        raise ValueError(f"{caller}: the resident LM path always folds "
                         f"snapshot refreshes into the compiled chunks; "
                         f"device_transitions=False applies to runner.run")


def train_loop(cfg: ModelConfig,
               prox: prox_lib.Prox,
               schedule: graphs.MixingSchedule,
               data,
               tc: TrainerConfig,
               snapshot_batch_iter=None,
               mesh=None, plan=None,
               exec: "ExecSpec | None" = None, *,
               resident=UNSET,
               sampling=UNSET,
               tracker=None,
               resume: bool = False) -> dict:
    """Returns the history dict (``step``/``loss``/``v_norm``/``alpha``/
    ``wire_bytes``/``time`` columns, plus ``final_state`` and the
    ``transfers`` ledger).

    ``data`` is an :class:`~repro.data.loader.LMLoader` (both execution
    paths, resume support, loader-stream snapshot batches of
    ``per_node_batch * snapshot_batch_mult`` windows) or a legacy iterator
    of stacked per-node batch dicts (host path only;
    ``snapshot_batch_iter`` then supplies the outer-loop refresh batches,
    defaulting to ``data``).

    ``exec`` is the same :class:`~repro.core.exec_spec.ExecSpec`
    ``runner.run`` consumes; its ``resident``/``sampling`` fields default
    to the corresponding ``TrainerConfig`` fields, its ``gossip``/``mesh``
    override ``tc.gossip`` and the positional ``mesh=`` when set
    (``gossip="auto"`` defers to ``tc.gossip``).  The bare ``resident=``/
    ``sampling=`` keywords are a deprecated one-release shim.  ``tracker``
    falls back to ``tc.tracker``."""
    spec = exec_spec_lib.resolve_exec(
        exec, "train_loop",
        defaults={"resident": tc.resident, "sampling": tc.sampling},
        resident=resident, sampling=sampling)
    _check_lm_spec(spec, "train_loop")
    if spec.shard == "cells":
        raise ValueError("shard='cells' partitions a hyperparameter grid's "
                         "cell axis — use train_sweep for batched λ/lr "
                         "grids; train_loop drives a single configuration")
    if spec.shard == "nodes":
        raise ValueError("the resident LM path does not support sharded "
                         "state (shard='nodes') yet — use the host loop "
                         "with mesh/plan")
    resident, sampling = spec.resident, spec.sampling
    if mesh is None:
        mesh = spec.mesh
    gossip = tc.gossip if spec.gossip == "auto" else spec.gossip

    m = schedule.m
    rule = algo_lib.UPDATE_RULES[tc.algorithm] \
        if isinstance(tc.algorithm, str) else tc.algorithm
    vr = rule.needs_snapshot
    alpha_fn = _realized_alpha_fn(tc, rule)

    is_loader = isinstance(data, loader_lib.LMLoader)
    if resident and not is_loader:
        raise ValueError(
            "resident=True plans the whole run up front, which needs the "
            "LMLoader's index-based sampling — pass the loader itself, not "
            "a batch iterator")
    if resident and (mesh is not None or plan is not None):
        raise ValueError("the resident LM path does not support sharded "
                         "state (mesh/plan) yet — use the host loop")
    if resume and not (tc.ckpt_dir and is_loader):
        raise ValueError("resume=True needs ckpt_dir and an LMLoader (the "
                         "checkpoint stores the loader's data cursor)")
    if is_loader and snapshot_batch_iter is not None:
        raise ValueError(
            "snapshot_batch_iter is not supported with an LMLoader: both "
            "execution paths draw snapshot batches from the loader's own "
            "stream (per_node_batch * snapshot_batch_mult windows) — pass a "
            "legacy batch iterator as `data` to control snapshot batches")
    device_sampling = resident and sampling == "device"

    # the transport backend owns the wire format: its per-step phi pytree
    # flows into the jitted train step, which dispatches the mix on its
    # type; stateful transports thread their state via TrainState.mix_state
    tmeta = transport.TransportMeta.constant(tc.consensus_rounds)
    backend = transport.resolve_backend(gossip, schedule, tmeta, mesh)
    gaux = backend.prepare(schedule, tmeta, mesh=mesh)
    bundle = steps_lib.build_train_step(cfg, prox, m, plan=plan, mesh=mesh,
                                        algorithm=rule, donate=False)

    state = bundle.init_state(jax.random.PRNGKey(tc.seed))
    if backend.needs_mix_state:
        state = state._replace(
            mix_state=backend.init_mix_state(gaux, state.params))
    key = jax.random.fold_in(jax.random.PRNGKey(tc.seed), 1) \
        if device_sampling else None
    param_count = transport.node_param_count(state.params)

    transfers = runner_lib._new_ledger()
    run_id = spans.next_run()
    start_step, slot, wire = 0, 0, 0
    if resume:
        template = {"state": state}
        if device_sampling:
            template["key"] = key
        tree, _, md = ckpt_lib.restore(tc.ckpt_dir, template)
        restored = [tree["state"], tree["key"]] if device_sampling \
            else [tree["state"]]
        h2d = runner_lib._moved(transfers, "h2d", restored)
        with spans.span(spans.STAGE, run=run_id, h2d_bytes=h2d, d2h_bytes=0):
            state = jax.tree.map(jnp.asarray, tree["state"])
            if device_sampling:
                key = jnp.asarray(tree["key"])
        start_step = int(md["step"])
        slot = int(md["slot"])
        wire = int(md["wire"])
        if md.get("loader") is not None:
            data.load_state_dict(md["loader"])

    history = HistoryTracker()
    track = CompositeTracker(
        [history] + resolve_tracker(tracker if tracker is not None
                                    else tc.tracker))

    t0 = time.time()

    def record(step: int, loss, v_norm, alpha, wire_now: int):
        track.log_metrics({"loss": float(loss), "v_norm": float(v_norm),
                           "alpha": float(alpha), "wire_bytes": wire_now,
                           "time": time.time() - t0}, step=step)

    def is_record(step: int) -> bool:
        return step % tc.log_every == 0 or step == tc.num_steps - 1

    def is_ckpt(step: int) -> bool:
        return bool(tc.ckpt_dir and tc.ckpt_every
                    and (step + 1) % tc.ckpt_every == 0)

    def save_ckpt(cur_state, cur_key, next_step: int, *,
                  slot_at: int | None = None, wire_at: int | None = None,
                  loader_state: dict | None = None):
        """Write a resumable checkpoint.  The host loop's live ``slot``/
        ``wire``/loader cursor ARE the values at the save point, so the
        defaults suffice; the resident path plans (and thus advances all
        three to end-of-run) before executing, so its periodic saves pass
        the per-chunk boundary values explicitly."""
        live = [cur_state, cur_key] if device_sampling else [cur_state]
        d2h = runner_lib._moved(transfers, "d2h", live)
        with spans.span(spans.CKPT, run=run_id, d2h_bytes=d2h):
            tree = {"state": jax.device_get(cur_state)}
            if device_sampling:
                tree["key"] = jax.device_get(cur_key)
            if loader_state is None and is_loader:
                loader_state = data.state_dict()
            md = {"step": next_step,
                  "slot": slot if slot_at is None else slot_at,
                  "wire": wire if wire_at is None else wire_at,
                  "algorithm": rule.name,
                  "loader": loader_state}
            ckpt_lib.save(tc.ckpt_dir, next_step, tree, md,
                          keep_last=tc.keep_last)

    # ------------------------------------------------------------------
    # host loop
    # ------------------------------------------------------------------
    if not resident:
        if is_loader:
            def next_batch():
                t, l = data.sample()
                return {"tokens": t, "labels": l}

            def next_big():
                starts = data.sample_starts(
                    data.per_node_batch * tc.snapshot_batch_mult)
                t, l = data.gather(starts)
                return {"tokens": t, "labels": l}
        else:
            batch_it = iter(data)
            snap_it = iter(snapshot_batch_iter) if snapshot_batch_iter \
                is not None else batch_it
            next_batch = lambda: next(batch_it)
            next_big = lambda: next(snap_it)

        for step in range(start_step, tc.num_steps):
            staged = []
            if vr and step % tc.snapshot_every == 0:
                big = jax.tree.map(jnp.asarray, next_big())
                staged.append(big)
                state = bundle.snapshot_step(state, big)
            batch = jax.tree.map(jnp.asarray, next_batch())
            phi = backend.phi_for(gaux, slot, tc.consensus_rounds)
            wire += backend.bytes_per_step(gaux, phi, param_count)
            slot += tc.consensus_rounds
            phi = _to_device_floats(phi)
            # per-step batch/phi (and snapshot batch) staging
            runner_lib._moved(transfers, "h2d", staged + [batch, phi])
            alpha = alpha_fn(step)
            state, metrics = bundle.train_step(
                state, batch, phi, jnp.float32(alpha))
            if is_record(step):
                record(step, metrics["loss"], metrics["v_norm"], alpha, wire)
                runner_lib._moved(transfers, "d2h",
                                  [metrics["loss"], metrics["v_norm"]])
            if is_ckpt(step):
                save_ckpt(state, None, step + 1)
    # ------------------------------------------------------------------
    # resident path: plan -> stage once -> donated chunk dispatches
    # ------------------------------------------------------------------
    else:
        B = data.per_node_batch
        snap_B = B * tc.snapshot_batch_mult
        host_sampling = not device_sampling

        with spans.span(spans.PLAN, run=run_id) as plan_span:
            chunks: list[_LMChunk] = []
            cur: dict[str, list] = {k: [] for k in
                                    ("starts", "sstarts", "snaps", "phis",
                                     "alphas")}
            alpha = 0.0
            for step in range(start_step, tc.num_steps):
                snap = vr and step % tc.snapshot_every == 0
                if host_sampling:
                    if vr:
                        # draw order matches the host loop exactly: snapshot
                        # windows first (when refreshing), then the minibatch
                        cur["sstarts"].append(
                            data.sample_starts(snap_B) if snap
                            else np.zeros((m, snap_B), np.int64))
                    cur["starts"].append(data.sample_starts(B))
                if vr:
                    cur["snaps"].append(snap)
                phi = backend.phi_for(gaux, slot, tc.consensus_rounds)
                wire += backend.bytes_per_step(gaux, phi, param_count)
                slot += tc.consensus_rounds
                cur["phis"].append(phi)
                alpha = alpha_fn(step)
                cur["alphas"].append(alpha)
                if is_record(step) or is_ckpt(step) \
                        or step == tc.num_steps - 1:
                    phis = jax.tree.map(lambda *l: runner_lib._stack_wire(l),
                                        *cur["phis"])
                    alphas = np.asarray(cur["alphas"], np.float32)
                    if host_sampling:
                        starts = np.stack(cur["starts"]).astype(np.int32)
                        if vr:
                            sstarts = np.stack(
                                cur["sstarts"]).astype(np.int32)
                            xs = (starts, sstarts,
                                  np.asarray(cur["snaps"], np.bool_), phis,
                                  alphas)
                        else:
                            xs = (starts, phis, alphas)
                    else:
                        snaps = np.asarray(cur["snaps"], np.bool_)
                        xs = (snaps, phis, alphas) if vr else (phis, alphas)
                    # ckpt boundaries snapshot the loader cursor HERE: at
                    # this point planning has drawn exactly the starts the
                    # host loop would have consumed through `step`, which is
                    # the cursor a mid-run resume must restore (the live
                    # state_dict after planning completes is the END-of-run
                    # cursor)
                    chunks.append(_LMChunk(
                        xs=xs, length=len(cur["alphas"]), last_step=step,
                        record=is_record(step),
                        ckpt_next=step + 1 if is_ckpt(step) else None,
                        alpha_last=alpha, wire_end=wire, slot_end=slot,
                        loader_state=data.state_dict() if is_ckpt(step)
                        else None))
                    cur = {k: [] for k in cur}
            plan_span.set_metadata(steps=tc.num_steps - start_step,
                                   chunks=len(chunks))

        exec_chunk = _make_lm_exec(bundle, vr=vr, sampling=sampling,
                                   seq_len=data.seq_len, batch=B,
                                   snap_batch=snap_B)

        # ONE staging transfer ships every chunk's xs plus the resident
        # token-shard buffer; nothing per-step crosses the host boundary
        # thereafter
        xs_all = [ch.xs for ch in chunks]
        runner_lib._warn_staging(runner_lib._nbytes(xs_all))
        shards = data.stacked_shards()
        h2d = runner_lib._moved(transfers, "h2d", (xs_all, shards))
        with spans.span(spans.STAGE, run=run_id, h2d_bytes=h2d, d2h_bytes=0):
            staged, shards_dev = jax.device_put((xs_all, shards))
            state = runner_lib._shield_for_donation(state)
        carry = (state, key) if device_sampling else state
        with spans.span(spans.DISPATCH, run=run_id, dispatches=len(chunks)):
            for i, ch in enumerate(chunks):
                with runner_lib._RESIDENT_DISPATCH_GUARD():
                    carry, ys = exec_chunk(carry, staged[i], shards_dev)
                if ch.record:
                    d2h = runner_lib._moved(transfers, "d2h", ys)
                    with spans.span(spans.PULL, run=run_id, d2h_bytes=d2h):
                        losses, vnorms = jax.device_get(ys)   # one a window
                    record(ch.last_step, losses[ch.length - 1],
                           vnorms[ch.length - 1], ch.alpha_last, ch.wire_end)
                if ch.ckpt_next is not None:
                    cur_state, cur_key = (carry if device_sampling
                                          else (carry, None))
                    save_ckpt(cur_state, cur_key, ch.ckpt_next,
                              slot_at=ch.slot_end, wire_at=ch.wire_end,
                              loader_state=ch.loader_state)
        state = carry[0] if device_sampling else carry
        if device_sampling:
            key = carry[1]

    # final checkpoint (skipped when the periodic cadence just wrote it)
    if tc.ckpt_dir and start_step < tc.num_steps and \
            not (tc.ckpt_every and tc.num_steps % tc.ckpt_every == 0):
        save_ckpt(state, key, tc.num_steps)

    losses = history.history().get("loss", [])
    track.log_summary({
        "algorithm": rule.name, "steps": tc.num_steps,
        "resident": resident, "sampling": sampling,
        "final_loss": losses[-1] if losses else None,
        "wire_bytes": wire, "wall_time": time.time() - t0,
        "transfers": dict(transfers),
    })
    track.finish()

    hist = history.history()
    hist["final_state"] = state
    hist["transfers"] = dict(transfers)
    return hist


# ---------------------------------------------------------------------------
# Batched λ/lr-grid sweeps (one device program for the whole grid)
# ---------------------------------------------------------------------------

def train_sweep(cfg: ModelConfig,
                build,
                schedule: graphs.MixingSchedule,
                data,
                tc: TrainerConfig,
                grid: dict,
                exec: "ExecSpec | None" = None,
                mode: str = "product") -> dict:
    """Train the whole hyperparameter grid as ONE resident device program.

    ``build(**cell) -> Prox`` is the cell factory (``prox.l1(lam)``,
    elastic-net pairs, ...): called once per cell with concrete values for
    validation and once INSIDE the batched trace with traced values
    (``run_sweep``'s tracer-rebuild trick), so each vmapped cell computes
    its own regularizer from its own scalars.  ``grid`` maps axis names to
    numeric value lists; the reserved axis ``"alpha"`` is driver-level —
    it overrides ``tc.alpha`` in the cell's realized step-size schedule
    (step sizes are host-planned into a staged ``(steps, cells)`` column)
    and is NOT passed to ``build``.

    Every cell sees the SAME loader stream ``data`` (drawn once, host-side,
    in ``train_loop``'s planning order), so cell i's history equals a
    sequential ``train_loop(exec=ExecSpec(resident=True))`` over a fresh
    same-seed loader to float tolerance.  The grid ships in one staging
    transfer, runs through one donated vmapped ``lax.scan`` executor, and
    pulls one stacked metrics tree — O(1) transfers for the whole sweep.
    ``exec`` defaults to ``ExecSpec(resident=True)``;
    ``ExecSpec(shard="cells")`` partitions the cell axis over a device
    mesh exactly as in ``runner.run_sweep``.

    Returns ``{"grid", "step", "loss", "v_norm", "alpha", "wire_bytes",
    "final_state", "transfers"}`` with ``(records, cells)`` metric columns.
    """
    spec = exec_spec_lib.resolve_exec(exec, "train_sweep",
                                      defaults={"resident": True})
    _check_lm_spec(spec, "train_sweep")
    if not spec.resident:
        raise ValueError("train_sweep is a batched device-resident program "
                         "(the grid rides one vmapped executor); for "
                         "sequential cells loop train_loop")
    if spec.sampling != "host":
        raise ValueError("train_sweep stages ONE shared host-drawn loader "
                         "stream so every cell sees the draws a sequential "
                         "train_loop would; sampling='device' is not "
                         "supported")
    if spec.shard == "nodes":
        raise ValueError("shard='nodes' partitions a single run's node "
                         "axis — train_sweep partitions the CELL axis "
                         "(shard='cells')")
    if tc.ckpt_dir or tc.tracker:
        raise ValueError("train_sweep neither checkpoints nor streams "
                         "trackers — run cells through train_loop for "
                         "those")
    if not isinstance(data, loader_lib.LMLoader):
        raise ValueError("train_sweep plans the whole run up front, which "
                         "needs the LMLoader's index-based sampling")
    shard, mesh = spec.shard, spec.mesh
    gossip = tc.gossip if spec.gossip == "auto" else spec.gossip

    cells = sweep_lib.expand_grid(grid, mode)
    n_cells = len(cells)
    axis_names = [n for n in grid if n != "alpha"]
    m = schedule.m
    rule = algo_lib.UPDATE_RULES[tc.algorithm] \
        if isinstance(tc.algorithm, str) else tc.algorithm
    vr = rule.needs_snapshot
    alpha_fns = [_realized_alpha_fn(
        dataclasses.replace(tc, alpha=float(c.get("alpha", tc.alpha))), rule)
        for c in cells]

    def cell_prox(cell):
        out = build(**{k: v for k, v in cell.items() if k != "alpha"})
        if not isinstance(out, prox_lib.Prox):
            raise TypeError(f"build(**cell) must return a Prox, got "
                            f"{type(out).__name__}")
        return out

    proxes = [cell_prox(c) for c in cells]   # concrete validation pass

    tmeta = transport.TransportMeta.constant(tc.consensus_rounds)
    gossip_mesh = None if shard == "cells" else mesh
    backend = transport.resolve_backend(gossip, schedule, tmeta, gossip_mesh)
    if shard == "cells" and sweep_lib._mesh_collective(backend):
        raise ValueError(
            f"shard='cells' partitions the CELL axis over the mesh, but "
            f"the {backend.name!r} transport mixes through node-axis mesh "
            f"collectives — use gossip='dense' or 'banded'")
    gaux = backend.prepare(schedule, tmeta, mesh=gossip_mesh)

    bundle0 = steps_lib.build_train_step(cfg, proxes[0], m, algorithm=rule,
                                         donate=False)
    state0 = bundle0.init_state(jax.random.PRNGKey(tc.seed))
    if backend.needs_mix_state:
        state0 = state0._replace(
            mix_state=backend.init_mix_state(gaux, state0.params))
    param_count = transport.node_param_count(state0.params)

    # host planning: ONE shared draw stream + phi schedule, per-cell alpha
    # columns realized into a staged (steps, cells) array
    Bn = data.per_node_batch
    snap_B = Bn * tc.snapshot_batch_mult
    starts_l, sstarts_l, snaps_l, phis_l, wire_l = [], [], [], [], []
    alphas = np.empty((tc.num_steps, n_cells), np.float32)
    slot, wire = 0, 0
    for step in range(tc.num_steps):
        snap = vr and step % tc.snapshot_every == 0
        if vr:
            # draw order matches train_loop exactly: snapshot windows first
            sstarts_l.append(data.sample_starts(snap_B) if snap
                             else np.zeros((m, snap_B), np.int64))
            snaps_l.append(snap)
        starts_l.append(data.sample_starts(Bn))
        phi = backend.phi_for(gaux, slot, tc.consensus_rounds)
        wire += backend.bytes_per_step(gaux, phi, param_count)
        slot += tc.consensus_rounds
        phis_l.append(phi)
        wire_l.append(wire)
        for j, fn in enumerate(alpha_fns):
            alphas[step, j] = fn(step)

    phis = jax.tree.map(lambda *l: runner_lib._stack_wire(l), *phis_l)
    starts = np.stack(starts_l).astype(np.int32)
    if vr:
        xs = (starts, np.stack(sstarts_l).astype(np.int32),
              np.asarray(snaps_l, np.bool_), phis, alphas)
        xs_axes = (None, None, None, None, 1)
    else:
        xs = (starts, phis, alphas)
        xs_axes = (None, None, 1)

    cache_key = ("train_sweep", cfg, build, rule.name, vr, data.seq_len,
                 Bn, snap_B, tuple(axis_names))

    def make():
        L = data.seq_len

        def gather(shards, st):
            win = jax.vmap(
                lambda row, s: row[s[:, None]
                                   + jnp.arange(L + 1)[None, :]])(shards, st)
            return {"tokens": win[..., :L], "labels": win[..., 1:]}

        @functools.partial(jax.jit, donate_argnums=0)
        def exec_sweep(carry, xs, shards, cells_d):
            def one_cell(state_c, xs_c, cell):
                # tracer rebuild: the cell's prox from its traced scalars;
                # _build_train_step bypasses the bundle cache (a traced
                # prox hashes by closure identity — caching it would pin
                # tracers past the trace)
                with algo_lib.ephemeral_steps():
                    prox_t = cell_prox(cell)
                    bundle_t = steps_lib._build_train_step(
                        cfg, prox_t, m, None, None, rule, False)

                def body(state, xs_step):
                    if vr:
                        st, sst, snap, phi, alpha = xs_step
                        state = jax.lax.cond(
                            snap,
                            lambda s: bundle_t.snapshot_step(
                                s, gather(shards, sst)),
                            lambda s: s, state)
                    else:
                        st, phi, alpha = xs_step
                    state, mets = bundle_t.train_step(
                        state, gather(shards, st), phi, alpha)
                    return state, (mets["loss"], mets["v_norm"])

                return jax.lax.scan(body, state_c, xs_c)

            return jax.vmap(one_cell, in_axes=(0, xs_axes, 0))(
                carry, xs, cells_d)

        return exec_sweep

    exec_sweep = sweep_lib._shared_sweep_exec(cache_key, make)

    transfers = {"h2d": 0, "d2h": 0}
    state_b = runner_lib._shield_for_donation(
        jax.tree.map(lambda l: jnp.stack([l] * n_cells), state0))
    cells_arr = sweep_lib._cell_arrays(cells, axis_names)
    shards = data.stacked_shards()
    staged_bytes = sum(np.asarray(leaf).nbytes
                       for leaf in jax.tree.leaves(xs))
    runner_lib._warn_staging(staged_bytes, cells=n_cells)

    if shard == "cells":
        smesh, caxis = sweep_lib._cells_mesh(mesh, n_cells)
        NS, PS = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
        rep = NS(smesh, PS())
        cell0 = NS(smesh, PS(caxis))
        cell1 = NS(smesh, PS(None, caxis))
        xs_sh = tuple(jax.tree.map(lambda _, s=s: s, x)
                      for x, s in zip(xs, [cell1 if a == 1 else rep
                                           for a in xs_axes]))
        xs_dev, shards_dev, cells_dev = jax.device_put(
            (xs, shards, cells_arr),
            (xs_sh, jax.tree.map(lambda _: rep, shards),
             {n: cell0 for n in cells_arr}))
        state_b = jax.device_put(state_b,
                                 jax.tree.map(lambda _: cell0, state_b))
    else:
        xs_dev, shards_dev, cells_dev = jax.device_put(
            (xs, shards, cells_arr))
    transfers["h2d"] += 1

    t0 = time.time()
    with runner_lib._RESIDENT_DISPATCH_GUARD():
        state_b, ys = exec_sweep(state_b, xs_dev, shards_dev, cells_dev)
    losses, vnorms = jax.device_get(ys)        # the ONE metrics pull, (B, T)
    transfers["d2h"] += 1

    rec = [s for s in range(tc.num_steps)
           if s % tc.log_every == 0 or s == tc.num_steps - 1]
    return {
        "grid": cells,
        "step": rec,
        "loss": np.asarray(losses, np.float64)[:, rec].T,
        "v_norm": np.asarray(vnorms, np.float64)[:, rec].T,
        "alpha": alphas[rec],
        "wire_bytes": [wire_l[s] for s in rec],
        "time": time.time() - t0,
        "final_state": state_b,
        "transfers": dict(transfers),
    }
