"""The unified `DecentralizedAlgorithm` protocol.

Every decentralized method in the repo — DPSVRG (paper Algorithm 1), DSPG
[paper ref. 11], DPG [ref. 10], GT-SVRG [refs 18/19], and the beyond-paper
loopless DPSVRG — is expressed as the same three pure transitions over an
algorithm-private state pytree with stacked node parameters (leading axis m):

    init()                        -> state        (all nodes at x0)
    step(state, batch, phi, a)    -> state        (one inner iteration)
    outer(state)                  -> state        (snapshot / full-grad refresh)
    end_outer(state, K)           -> state        (close an inner round, e.g.
                                                   Algorithm 1's tail average)

plus declarative :class:`AlgoMeta` (loop structure, gradient-evaluation cost
per step, gossip-rounds policy, step-size schedule, metric conventions).  The
single driver in :mod:`repro.core.runner` consumes this protocol and owns
everything the old bespoke ``*_run`` loops copy-pasted: batch sampling,
time-varying gossip scheduling, epoch/communication accounting, metric
recording, and an optional ``lax.scan`` fast path.

A new baseline is now a ~50-line factory returning an :class:`Algorithm`;
register it in :data:`ALGORITHMS` and it runs on every problem, schedule,
benchmark, and recorder in the repo.

The LM-scale trainer (``repro.train.steps``) shares the inner update via
:data:`UPDATE_RULES` + :func:`prox_gossip_update` instead of re-implementing
the SVRG correction a third time.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import compression, gossip, prox as prox_lib, schedules, spans, \
    svrg, transport
from ..kernels.fused_update import ops as fused_ops

__all__ = [
    "Problem",
    "UpdateRule",
    "UPDATE_RULES",
    "prox_gossip_update",
    "AlgoMeta",
    "Algorithm",
    "ephemeral_steps",
    "DPSVRGHyperParams",
    "DSPGHyperParams",
    "build_node_grad_fn",
    "build_node_full_grad_fn",
    "build_dpsvrg_inner_step",
    "build_dspg_step",
    "build_gt_svrg_inner_step",
    "build_dvr_inner_step",
    "build_fused_svrg_inner",
    "build_fused_sgd_step",
    "dpsvrg_algorithm",
    "dspg_algorithm",
    "dpg_algorithm",
    "gt_svrg_algorithm",
    "loopless_dpsvrg_algorithm",
    "dvr_algorithm",
    "ALGORITHMS",
]


# ---------------------------------------------------------------------------
# Problem: what all algorithms run against
# ---------------------------------------------------------------------------

class Problem(NamedTuple):
    """A decentralized composite problem min F = (1/m) sum_i f_i + h.

    loss_fn:      ``loss_fn(params, batch) -> scalar`` per-node smooth loss
    prox:         the non-smooth regularizer's proximal operator
    x0:           stacked start point, leaves (m, ...)
    full_data:    per-node datasets, leaves (m, n, ...)
    objective_fn: optional override for the recorded objective F(x_bar)
    """
    loss_fn: Callable
    prox: prox_lib.Prox
    x0: Any
    full_data: Any
    objective_fn: Callable | None = None


# ---------------------------------------------------------------------------
# Hyper-parameters (canonical home; re-exported by core.dpsvrg for compat)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPSVRGHyperParams:
    alpha: float = 0.01          # constant step size (the VR payoff)
    beta: float = 1.07           # inner-loop growth base
    n0: int = 8                  # initial inner-loop length
    num_outer: int = 30          # S
    batch_size: int = 1          # paper uses single-sample inner steps
    k_max: int | None = None     # multi-consensus cap (None = faithful, k rounds at step k)
    single_consensus: bool = False  # Fig.3 ablation: one gossip round per step
    compress_bits: int | None = None  # int-quantized gossip w/ error feedback


@dataclasses.dataclass(frozen=True)
class DSPGHyperParams:
    alpha0: float = 0.01
    decay: float = 0.5           # alpha_k = alpha0 / (k+1)^decay
    batch_size: int = 1
    constant_step: bool = False  # with a constant step DSPG stalls (inexact convergence)


# ---------------------------------------------------------------------------
# Update rules: the loss-agnostic inner update shared with the LM trainer
# ---------------------------------------------------------------------------

class UpdateRule(NamedTuple):
    """Gradient-direction rule of the shared prox-gossip update.

    ``direction(g_now, g_snap, mu) -> v`` computes the descent direction from
    the minibatch gradient at the iterate, the minibatch gradient at the
    snapshot, and the snapshot full gradient.  Rules that don't need the
    snapshot (``needs_snapshot=False``) receive ``None`` for the latter two.
    """
    name: str
    needs_snapshot: bool
    direction: Callable


def _svrg_direction(g_now, g_snap, mu):
    return jax.tree.map(lambda a, b, c: a - b + c, g_now, g_snap, mu)


def _sgd_direction(g_now, g_snap, mu):
    return g_now


DPSVRG_RULE = UpdateRule("dpsvrg", True, _svrg_direction)
DSPG_RULE = UpdateRule("dspg", False, _sgd_direction)

UPDATE_RULES: dict[str, UpdateRule] = {
    "dpsvrg": DPSVRG_RULE,
    "dspg": DSPG_RULE,
}


def prox_gossip_update(params, v, phi, alpha, prox: prox_lib.Prox,
                       mix_fn: Callable = gossip.mix_stacked):
    """Algorithm 1 lines 8-11 for all nodes at once (shared hot path):

        q     = x - alpha * v
        q_hat = gossip(phi, q)
        x'    = prox_h^alpha(q_hat)

    The default ``mix_fn`` (``gossip.mix_stacked``) dispatches on the phi's
    wire format (dense / ``BandedPhi`` / ``PermutePhi``), so the same update
    serves every stateless transport backend; ``mix_fn`` stays pluggable for
    callers that need a bespoke collective.
    """
    with spans.scope(spans.UPDATE):
        q = jax.tree.map(lambda x, vi: x - alpha * vi.astype(x.dtype),
                         params, v)
    q_hat = mix_fn(phi, q)
    with spans.scope(spans.PROX):
        return prox.apply(q_hat, alpha)


# ---------------------------------------------------------------------------
# Gradient function builders (stacked over nodes via vmap)
# ---------------------------------------------------------------------------

def build_node_grad_fn(loss_fn: Callable) -> Callable:
    """loss_fn(params, batch)->scalar  =>  grad over stacked params.

    Stacked signature: params leaves (m, ...), batch leaves (m, B, ...).
    vmap over the node axis keeps each node's gradient private, exactly as in
    decentralized learning — under GSPMD the vmapped axis is the node mesh
    axis, so no cross-node communication happens here.
    """
    g = jax.vmap(jax.grad(loss_fn))

    def node_grad(params, batch):
        with spans.scope(spans.GRAD):
            return g(params, batch)

    return node_grad


def build_node_full_grad_fn(loss_fn: Callable, full_batch) -> Callable:
    """Full local gradient closure over each node's entire dataset."""
    g = jax.vmap(jax.grad(loss_fn))

    def full_grad(params):
        return g(params, full_batch)

    return full_grad


# ---------------------------------------------------------------------------
# Jitted step builders
# ---------------------------------------------------------------------------

# Step functions are memoized on their (hashable) ingredients so that
# REBUILDING an Algorithm — as every sweep point does — returns the SAME
# function objects, and therefore the same jax.jit compilation caches and the
# same runner chunk executors.  This is what lets compiled scan/resident
# chunks survive across ``runner.run`` calls: the executable cache in
# ``core.runner`` keys on step identity, and step identity is stable across
# instances with identical loss/prox closures.  Entries hold no datasets
# (data-bound steps like DPG's full-gradient step are deliberately NOT
# memoized), so the LRU cap only bounds compiled-code retention.
_SHARED_STEPS: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()
_SHARED_STEPS_MAX = 128

# When True, _shared_step builds fresh functions WITHOUT touching the global
# LRU.  The batched sweep executor rebuilds algorithms INSIDE a trace (cell
# hyperparameters arrive as tracers, e.g. a vmapped lambda grid), and those
# tracer-closing steps must never be cached: their keys embed fresh Prox
# objects so they could never be served again, but they would still evict
# legitimate entries and pin tracers past their trace.
_EPHEMERAL_STEPS = False


@contextlib.contextmanager
def ephemeral_steps():
    """Build algorithm steps without memoizing them (in-trace rebuilds)."""
    global _EPHEMERAL_STEPS
    prev = _EPHEMERAL_STEPS
    _EPHEMERAL_STEPS = True
    try:
        yield
    finally:
        _EPHEMERAL_STEPS = prev


def memoize_into(cache: "collections.OrderedDict", cap: int, key: tuple,
                 make: Callable[[], Callable]) -> Callable:
    """Bounded (LRU) build-on-miss memoizer — shared by the step cache here
    and the runner's executable cache."""
    fn = cache.get(key)
    if fn is None:
        fn = make()
        cache[key] = fn
        while len(cache) > cap:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fn


def _shared_step(key: tuple, make: Callable[[], Callable]) -> Callable:
    if _EPHEMERAL_STEPS:
        return make()
    return memoize_into(_SHARED_STEPS, _SHARED_STEPS_MAX, key, make)


def build_dpsvrg_inner_step(loss_fn: Callable, prox: prox_lib.Prox,
                            compress_bits: int | None = None):
    """Returns jitted ``step(params, svrg_state, batch, phi, alpha, cstate)
    -> (params, cstate)`` implementing Algorithm 1 lines 7-11 for all nodes
    at once.  ``phi`` may be any transport wire format (dense, ``BandedPhi``,
    ``PermutePhi``, ``CompressedPhi``) — the mix dispatches on its type at
    trace time.  ``cstate`` is the error-feedback state for compressed
    gossip (None, returned untouched, for stateless transports).  The legacy
    ``compress_bits`` hyperparameter wraps the incoming phi in a
    ``CompressedPhi`` so hp-level compression and the ``compressed``
    transport backend share one code path.
    """
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        @jax.jit
        def step(params, svrg_state, batch, phi, alpha, cstate):
            if compress_bits is not None and \
                    not isinstance(phi, compression.CompressedPhi):
                phi = compression.CompressedPhi(phi, compress_bits)
            v = svrg.corrected_gradient(node_grad, params, svrg_state, batch)
            with spans.scope(spans.UPDATE):
                q = jax.tree.map(lambda x, vi: x - alpha * vi.astype(x.dtype),
                                 params, v)
            q_hat, cstate = compression.mix_with_state(phi, q, cstate)
            with spans.scope(spans.PROX):
                x = prox.apply(q_hat, alpha)
            return x, cstate

        return step

    return _shared_step(("dpsvrg_inner", loss_fn, prox, compress_bits), make)


def build_dspg_step(loss_fn: Callable, prox: prox_lib.Prox):
    """DSPG [paper ref. 11]: plain stochastic gradient + single gossip + prox,
    decaying step size."""
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        @jax.jit
        def step(params, batch, w, alpha):
            g = node_grad(params, batch)
            return prox_gossip_update(params, g, w, alpha, prox)

        return step

    return _shared_step(("dspg_step", loss_fn, prox), make)


def build_gt_svrg_inner_step(loss_fn: Callable, prox: prox_lib.Prox):
    """GT-SVRG inner update: prox-gossip step + gradient-tracking recursion.

    Both collectives (the iterate mix and the tracker mix) route through
    ``compression.mix_with_state``, so the step can ride the stateful
    ``compressed`` transport: ``cstate`` is a pair of error-feedback states
    (one per transmitted quantity — iterate and tracker carry independent
    quantization residuals), or ``None`` for stateless transports.
    """
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        @jax.jit
        def inner(params, tracker, v_prev, est, batch, w, a, cstate):
            cq, ct = cstate if cstate is not None else (None, None)
            with spans.scope(spans.UPDATE):
                q = jax.tree.map(lambda x, y: x - a * y, params, tracker)
            q_hat, cq = compression.mix_with_state(w, q, cq)
            with spans.scope(spans.PROX):
                new_params = prox.apply(q_hat, a)
            v_new = svrg.corrected_gradient(node_grad, new_params, est, batch)
            t_mixed, ct = compression.mix_with_state(w, tracker, ct)
            new_tracker = jax.tree.map(
                lambda ty, vn, vp: ty + vn - vp, t_mixed, v_new, v_prev)
            new_cstate = None if cq is None and ct is None else (cq, ct)
            return new_params, new_tracker, v_new, new_cstate

        return inner

    return _shared_step(("gt_svrg_inner", loss_fn, prox), make)


def build_dvr_inner_step(loss_fn: Callable, prox: prox_lib.Prox, rho: float):
    """Dual-Free DVR inner update (Hendrikx et al., arXiv 2006.14384),
    adapted to this runner's primal sampled-batch interface.

    Exact DVR runs dual-free coordinate ascent with a PER-SAMPLE dual table
    z_ij and needs the sampled indices j to update it; the runner's sampling
    contract hands steps batch VALUES only.  What this plugin keeps is DVR's
    structure that the paper's multi-consensus lacks: variance-reduced local
    computation DECOUPLED from a partial communication step with its own
    step size ``rho`` (DVR's p_comm-scaled gossip) —

        v  = SVRG-corrected gradient          (dual-free VR surrogate)
        y  = x - alpha v                      (local computation step)
        x' = prox_h((1-rho) y + rho W y)      (damped gossip: rho = 1 is the
                                               usual full mixing, rho < 1
                                               trades consensus for staleness
                                               tolerance)

    The mix routes through ``compression.mix_with_state`` so DVR rides
    stateful transports (compressed / scenario) like the DPSVRG family.
    """
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        @jax.jit
        def step(params, est, batch, phi, alpha, cstate):
            v = svrg.corrected_gradient(node_grad, params, est, batch)
            with spans.scope(spans.UPDATE):
                y = jax.tree.map(lambda x, vi: x - alpha * vi.astype(x.dtype),
                                 params, v)
            y_mixed, cstate = compression.mix_with_state(phi, y, cstate)
            with spans.scope(spans.MIX):     # the damping is DVR's gossip
                q = jax.tree.map(lambda a, b: (1.0 - rho) * a + rho * b,
                                 y, y_mixed)
            with spans.scope(spans.PROX):
                return prox.apply(q, alpha), cstate

        return step

    return _shared_step(("dvr_inner", loss_fn, prox, rho), make)


# ---------------------------------------------------------------------------
# Fused resident-step twins (kernels.fused_update)
# ---------------------------------------------------------------------------
#
# ``runner.run(kernel="pallas"|"auto")`` swaps these into the compiled chunk
# body in place of the unfused steps.  They compute the SAME update —
# prox(W @ (x - alpha*v)) — through one fused kernel pass over the stacked
# (m, d) buffer instead of a chain of separate XLA ops, and fall back to the
# unfused step AT TRACE TIME whenever the configuration has no fused
# lowering:
#
# * the phi wire format has no static dense matrix (``transport.mix_matrix``
#   returns None: ppermute mesh collectives, compressed/scenario wrappers),
# * a stateful transport threads a mix state (cstate is not None),
# * the prox has no ``fused_spec`` (only l1 / sql2 / none lower),
# * mode="auto" at small per-node d, where the unfused XLA body wins
#   (``fused_ops.FUSED_MIN_D``).
#
# All checks are Python-level on static structure, so the fallback costs
# nothing in the compiled program.

def _fused_fallback(mode: str, prox: prox_lib.Prox, phi, cstate, params):
    """-> (dense W or None, fused spec or None); (None, None) = use the
    unfused step."""
    spec = prox.fused_spec
    if spec is None or cstate is not None:
        return None, None
    if mode == "auto" and not fused_ops.fused_wins(
            fused_ops.tree_node_dim(params)):
        return None, None
    w = transport.mix_matrix(phi)
    if w is None:
        return None, None
    return w, spec


def build_fused_svrg_inner(loss_fn: Callable, prox: prox_lib.Prox, mode: str,
                           rho: float | None = None):
    """Fused twin of ``build_dpsvrg_inner_step`` (rho=None) /
    ``build_dvr_inner_step`` (rho set: W_eff = (1-rho) I + rho W folds DVR's
    damped gossip into the kernel's mix matrix).  Same signature:
    ``inner(params, est, batch, phi, alpha, cstate) -> (params, cstate)``.
    """
    base = (build_dvr_inner_step(loss_fn, prox, rho) if rho is not None
            else build_dpsvrg_inner_step(loss_fn, prox))

    def make():
        node_grad = build_node_grad_fn(loss_fn)

        def inner(params, est, batch, phi, alpha, cstate):
            w, spec = _fused_fallback(mode, prox, phi, cstate, params)
            if w is None:
                return base(params, est, batch, phi, alpha, cstate)
            if rho is not None:
                w = (1.0 - rho) * jnp.eye(w.shape[0], dtype=w.dtype) + rho * w
            kind, lam = spec
            g_now = node_grad(params, batch)
            g_snap = node_grad(est.snapshot, batch)
            x = fused_ops.fused_resident_step(
                w, params, (g_now, g_snap, est.full_grad), alpha, lam,
                rule="svrg", prox_kind=kind)
            return x, cstate

        return inner

    return _shared_step(("fused_svrg_inner", loss_fn, prox, mode, rho), make)


def build_fused_sgd_step(loss_fn: Callable, prox: prox_lib.Prox, mode: str):
    """Fused twin of ``build_dspg_step``: one kernel pass for
    prox(W @ (x - alpha*g))."""
    base = build_dspg_step(loss_fn, prox)

    def make():
        node_grad = build_node_grad_fn(loss_fn)

        def step_fn(params, batch, phi, alpha):
            w, spec = _fused_fallback(mode, prox, phi, None, params)
            if w is None:
                return base(params, batch, phi, alpha)
            kind, lam = spec
            g = node_grad(params, batch)
            return fused_ops.fused_resident_step(
                w, params, (g,), alpha, lam, rule="sgd", prox_kind=kind)

        return step_fn

    return _shared_step(("fused_sgd_step", loss_fn, prox, mode), make)


# ---------------------------------------------------------------------------
# Protocol: declarative metadata + the state/step/outer triple
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AlgoMeta:
    """Everything the generic runner needs to know about a method, declared
    up front instead of encoded in a bespoke loop.

    Loop structure — exactly one of:
      outer_lengths: inner-round lengths (K_1, ..., K_S); the runner calls
                     ``outer()`` before each round and ``end_outer()`` after
      num_steps:     flat loop length (``outer()`` only on coin flips)

    Cost accounting (effective-epoch metric, per inner step):
      step_grad_factor: single-sample grad evals per node per batch element
                        (2 for SVRG-corrected steps, 1 for plain SGD)
      outer_full_grad:  charge m*n evals at each ``outer()`` refresh
      init_full_grad:   charge m*n evals at ``init()`` (loopless warm start)

    Gossip policy:
      gossip_rounds(k): consensus rounds at inner step k (in-round k for
                        outer/inner methods, global t for flat ones); the
                        runner turns rounds into one pre-multiplied Phi
      gossip_payloads:  distinct quantities transmitted per mixing (wire
                        accounting multiplier): 1 for prox-gossip methods,
                        2 for gradient tracking, which gossips the iterate
                        AND the tracking direction with the same Phi
      slot_start:       first slot of the time-varying schedule consumed

    Recording conventions (kept method-by-method identical to the historical
    loops so downstream figure scripts are unaffected):
      stepsize(t):      step size at global step t (1-based)
      snapshot_prob:    loopless coin-flip probability (flat loops only)
      track_consensus:  record mean ||x_i - x_bar|| (else zeros)
      comm_metric:      "gossip" (cumulative rounds) | "steps"
      epoch_metric:     "grad" (evals / (m n)) | "steps" (DPG: 1 epoch/step)
      record_key:       "round" | "global" — which counter record_every keys on
      final_record:     force a terminal record (deduplicated by the runner)

    Wire format:
      compress_bits:    the method itself quantizes its gossip payload at
                        this int width (error feedback threaded through the
                        algorithm state).  The runner wraps the resolved
                        transport in a CompressedBackend at these bits so
                        the wire-byte accounting matches what actually moves
                        (and raises if a conflicting compressed transport is
                        requested).

    Resident-mode metric contract (``runner.run(resident=True)``):
      resident_objective: traceable ``objective(stacked_params, full_data)
                        -> scalar`` evaluated INSIDE the jitted on-device
                        record kernel.  None (the default) means the
                        standard composite objective F(x̄) = mean_i
                        f_i(x̄) + h(x̄) via the vmap'd loss + prox value —
                        correct for every method in the repo.  Algorithms
                        whose recorded objective differs from F(x̄) declare
                        it here; the consensus column always comes from the
                        in-graph ``jnp`` norms when ``track_consensus`` is
                        set.  (``Problem.objective_fn`` still overrides on
                        the host paths, and is used by the resident path
                        too when set — but then it must be jax-traceable.)

    Fused-kernel eligibility (``runner.run(kernel="pallas"|"auto")``):
      fused_step:       ``fused_step(mode) -> step`` returning a step with
                        the standard ``(state, batch, phi, alpha) -> state``
                        signature whose inner update runs through the fused
                        resident-step kernel (``kernels.fused_update``) when
                        the traced configuration lowers, falling back to the
                        unfused step otherwise (see the fused-twin builders
                        above).  ``mode`` is "pallas" (fuse whenever a
                        lowering exists) or "auto" (additionally require the
                        shape to be in the kernel's winning regime).  None —
                        the default — declares the method has no fused
                        lowering (e.g. gradient tracking's two-payload step)
                        and the runner silently keeps the unfused body.
    """
    name: str
    stepsize: Callable[[int], float]
    outer_lengths: tuple[int, ...] | None = None
    num_steps: int | None = None
    batch_size: int = 1
    step_grad_factor: int = 1
    outer_full_grad: bool = False
    init_full_grad: bool = False
    gossip_rounds: Callable[[int], int] = lambda k: 1
    gossip_payloads: int = 1
    slot_start: int = 0
    snapshot_prob: float | None = None
    track_consensus: bool = False
    comm_metric: str = "steps"
    epoch_metric: str = "grad"
    record_key: str = "round"
    final_record: bool = True
    compress_bits: int | None = None
    resident_objective: Callable | None = None
    fused_step: Callable[[str], Callable] | None = None


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """A decentralized algorithm bound to a :class:`Problem`.

    ``step`` must be jit-compatible (the runner traces it under ``lax.scan``
    on the fast path); ``init``/``outer``/``end_outer`` run on host between
    dispatches and may mix eager and jitted work.

    ``init_mix_state`` opts the algorithm into STATEFUL gossip transports
    (the ``compressed`` backend's error-feedback residual): it injects a
    fresh mix state into an initialized algorithm state, and the step must
    thread that state through its mix (``compression.mix_with_state``).
    DPSVRG, GT-SVRG, and loopless DPSVRG all do (GT-SVRG carries one
    residual per transmitted quantity — iterate and tracker); algorithms
    leaving it None can only be driven by stateless transports.

    The TRACEABLE outer-transition contract (``outer_traced`` /
    ``end_outer_traced`` / ``device_state``) lets the runner fold the
    outer-round transitions into the compiled chunk program (``lax.cond``
    on a precomputed round schedule) instead of dispatching ``outer`` /
    ``end_outer`` from host between chunks — required for batched sweeps
    (``core.sweep``) and the default for ``runner.run(resident=True)``
    when declared:

    * ``outer_traced(state, full_data) -> state`` — same transition as
      ``outer`` but jit/vmap-safe with the dataset passed EXPLICITLY (so
      the compiled chunk reads the staged device-resident copy instead of
      baking the closed-over host array in as a constant) and a FIXED
      output pytree structure.
    * ``end_outer_traced(state, k) -> state`` — same as ``end_outer`` with
      the round length as a traced f32 scalar.
    * ``device_state(state) -> state`` — one-time host-side shim that gives
      the initial state the fixed structure the traced transitions need
      (e.g. DPSVRG's ``est=None`` becomes a zero-filled ``SvrgState``
      placeholder; it is overwritten by the first in-chunk ``outer`` before
      any step reads it).  None means the init state already has it.
    """
    meta: AlgoMeta
    init: Callable[[], Any]
    step: Callable[[Any, Any, Any, Any], Any]   # (state, batch, phi, alpha)
    outer: Callable[[Any], Any] | None = None
    end_outer: Callable[[Any, int], Any] | None = None
    rule: UpdateRule | None = None
    init_mix_state: Callable[[Any], Any] | None = None
    outer_traced: Callable[[Any, Any], Any] | None = None
    end_outer_traced: Callable[[Any, Any], Any] | None = None
    device_state: Callable[[Any], Any] | None = None

    @staticmethod
    def get_params(state):
        return state.params


# Algorithm-private states.  All carry stacked params; the rest is method
# bookkeeping that rides through ``lax.scan`` as part of the carry.

class ParamState(NamedTuple):
    params: Any


class DPSVRGState(NamedTuple):
    params: Any
    anchor: Any                       # snapshot point for the NEXT refresh
    est: svrg.SvrgState | None        # current snapshot + full gradient
    inner_sum: Any                    # tail-average accumulator (line 13)
    cstate: Any                       # compression error-feedback state


class GTSVRGState(NamedTuple):
    params: Any
    anchor: Any
    est: svrg.SvrgState | None
    tracker: Any                      # gradient-tracking direction y_i
    v_prev: Any
    inner_sum: Any
    cstate: Any = None                # (iterate, tracker) error-feedback pair


class LooplessState(NamedTuple):
    params: Any
    est: svrg.SvrgState
    cstate: Any = None                # compression error-feedback state


def _zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


# Traced outer transitions are memoized like the steps: rebuilt Algorithm
# instances with identical loss closures return the SAME function objects,
# so the runner's chunk executors (whose cache keys embed these identities)
# stay warm across sweep points.  They close over NO data — the dataset is
# an explicit argument, read from the staged device-resident copy.

def _svrg_outer_traced(loss_fn: Callable) -> Callable:
    """snapshot <- anchor, full_grad <- grad at anchor over the full data,
    inner_sum <- 0 — the traced twin of the DPSVRG/GT-SVRG ``outer``."""
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        def outer_traced(state, full_data):
            est = svrg.SvrgState(snapshot=state.anchor,
                                 full_grad=node_grad(state.anchor, full_data))
            return state._replace(est=est,
                                  inner_sum=_zeros_like(state.params))

        return outer_traced

    return _shared_step(("svrg_outer_traced", loss_fn), make)


def _tail_average_end_outer_traced() -> Callable:
    """anchor <- inner_sum / K (Algorithm 1 line 13) with K a traced f32."""
    def make():
        def end_outer_traced(state, k):
            return state._replace(
                anchor=jax.tree.map(lambda acc: acc / k, state.inner_sum))

        return end_outer_traced

    return _shared_step(("tail_average_end_outer",), make)


def _loopless_outer_traced(loss_fn: Callable) -> Callable:
    """Coin-flip snapshot refresh at the CURRENT iterate (L-SVRG style)."""
    def make():
        node_grad = build_node_grad_fn(loss_fn)

        def outer_traced(state, full_data):
            return state._replace(est=svrg.SvrgState(
                snapshot=state.params,
                full_grad=node_grad(state.params, full_data)))

        return outer_traced

    return _shared_step(("loopless_outer_traced", loss_fn), make)


def _svrg_placeholder_state(state):
    """Fixed-structure device state: fill ``est=None`` with a zero
    ``SvrgState`` placeholder (overwritten by the first in-chunk ``outer``
    before any step reads it)."""
    if state.est is not None:
        return state
    est = svrg.SvrgState(snapshot=state.anchor,
                         full_grad=_zeros_like(state.params))
    return state._replace(est=est)


# ---------------------------------------------------------------------------
# Factories: one per method, each a ~40-line plugin
# ---------------------------------------------------------------------------

def dpsvrg_algorithm(problem: Problem, hp: DPSVRGHyperParams) -> Algorithm:
    """Paper Algorithm 1: SVRG-corrected prox step + multi-consensus gossip,
    growing inner rounds K_s = ceil(beta^s n0), tail-average snapshots."""
    inner = build_dpsvrg_inner_step(problem.loss_fn, problem.prox,
                                    compress_bits=hp.compress_bits)
    full_grad_fn = build_node_full_grad_fn(problem.loss_fn, problem.full_data)

    def init():
        cstate = (compression.init_state(problem.x0)
                  if hp.compress_bits is not None else None)
        return DPSVRGState(params=problem.x0, anchor=problem.x0, est=None,
                           inner_sum=_zeros_like(problem.x0), cstate=cstate)

    def init_mix_state(state, make=compression.init_state):
        # the stateful transport threads its state through cstate; ``make``
        # defaults to the compressed backend's error-feedback residual, and
        # the runner passes the resolved backend's own initializer (bound to
        # its aux) for other stateful transports (scenario delay buffers)
        return state._replace(cstate=make(problem.x0))

    def outer(state):
        est = svrg.SvrgState(snapshot=state.anchor,
                             full_grad=full_grad_fn(state.anchor))
        return state._replace(est=est, inner_sum=_zeros_like(state.params))

    def make_step():
        def step(state, batch, phi, alpha):
            params, cstate = inner(state.params, state.est, batch, phi,
                                   alpha, state.cstate)
            return state._replace(
                params=params, cstate=cstate,
                inner_sum=svrg.tree_add(state.inner_sum, params))
        return step

    step = _shared_step(("dpsvrg_proto_step", inner), make_step)

    def fused_step(mode):
        finner = build_fused_svrg_inner(problem.loss_fn, problem.prox, mode)

        def make_fused():
            def fstep(state, batch, phi, alpha):
                params, cstate = finner(state.params, state.est, batch, phi,
                                        alpha, state.cstate)
                return state._replace(
                    params=params, cstate=cstate,
                    inner_sum=svrg.tree_add(state.inner_sum, params))
            return fstep

        return _shared_step(("dpsvrg_proto_fused", finner), make_fused)

    def end_outer(state, K):
        return state._replace(
            anchor=jax.tree.map(lambda acc: acc / K, state.inner_sum))

    if hp.single_consensus:
        rounds = lambda k: 1
    elif hp.k_max is None:
        rounds = lambda k: k
    else:
        rounds = lambda k: min(k, hp.k_max)

    meta = AlgoMeta(
        name="dpsvrg",
        stepsize=schedules.constant(hp.alpha),
        outer_lengths=tuple(
            schedules.inner_loop_lengths(hp.beta, hp.n0, hp.num_outer)),
        batch_size=hp.batch_size,
        step_grad_factor=2,
        outer_full_grad=True,
        gossip_rounds=rounds,
        track_consensus=True,
        comm_metric="gossip",
        record_key="round",
        final_record=True,
        compress_bits=hp.compress_bits,
        # hp-level quantization threads error feedback through every mix —
        # no fused lowering exists for that configuration
        fused_step=None if hp.compress_bits is not None else fused_step,
    )
    return Algorithm(meta=meta, init=init, step=step, outer=outer,
                     end_outer=end_outer, rule=DPSVRG_RULE,
                     init_mix_state=init_mix_state,
                     outer_traced=_svrg_outer_traced(problem.loss_fn),
                     end_outer_traced=_tail_average_end_outer_traced(),
                     device_state=_svrg_placeholder_state)


def dspg_algorithm(problem: Problem, hp: DSPGHyperParams,
                   num_steps: int) -> Algorithm:
    """DSPG baseline: one stochastic prox-gradient + one gossip per step."""
    step_fn = build_dspg_step(problem.loss_fn, problem.prox)

    def make_step():
        def step(state, batch, phi, alpha):
            return ParamState(step_fn(state.params, batch, phi, alpha))
        return step

    step = _shared_step(("dspg_proto_step", step_fn), make_step)

    def fused_step(mode):
        fstep_fn = build_fused_sgd_step(problem.loss_fn, problem.prox, mode)

        def make_fused():
            def fstep(state, batch, phi, alpha):
                return ParamState(fstep_fn(state.params, batch, phi, alpha))
            return fstep

        return _shared_step(("dspg_proto_fused", fstep_fn), make_fused)

    meta = AlgoMeta(
        name="dspg",
        stepsize=(schedules.constant(hp.alpha0) if hp.constant_step
                  else schedules.dspg_stepsize(hp.alpha0, hp.decay)),
        num_steps=num_steps,
        batch_size=hp.batch_size,
        step_grad_factor=1,
        slot_start=1,
        track_consensus=True,
        fused_step=fused_step,
    )
    return Algorithm(meta=meta, init=lambda: ParamState(problem.x0),
                     step=step, rule=DSPG_RULE)


def dpg_algorithm(problem: Problem, alpha: float, num_steps: int) -> Algorithm:
    """DPG [paper ref. 10]: deterministic full local gradients, one gossip +
    prox per step.  The smooth anchor: one effective epoch per step."""
    full_grad_fn = build_node_full_grad_fn(problem.loss_fn, problem.full_data)
    prox = problem.prox

    @jax.jit
    def _step(params, w, a):
        g = full_grad_fn(params)
        with spans.scope(spans.UPDATE):
            q = jax.tree.map(lambda x, gi: x - a * gi, params, g)
        q_hat = gossip.mix_stacked(w, q)
        with spans.scope(spans.PROX):
            return prox.apply(q_hat, a)

    def step(state, batch, phi, alpha):
        return ParamState(_step(state.params, phi, alpha))

    def fused_step(mode):
        # keyed on ``_step`` (unique per algorithm instance, so per dataset):
        # repeated runner.run calls must get the SAME fstep object back or
        # the resident-exec cache misses and every run retraces+recompiles
        # the chunk executor — at LM-scale d that recompile dwarfs the run
        def make_fused():
            def fstep(state, batch, phi, alpha):
                w, spec = _fused_fallback(mode, prox, phi, None,
                                          state.params)
                if w is None:
                    return ParamState(_step(state.params, phi, alpha))
                kind, lam = spec
                g = full_grad_fn(state.params)
                return ParamState(fused_ops.fused_resident_step(
                    w, state.params, (g,), alpha, lam, rule="sgd",
                    prox_kind=kind))
            return fstep

        return _shared_step(("dpg_proto_fused", _step, mode), make_fused)

    meta = AlgoMeta(
        name="dpg",
        stepsize=schedules.constant(alpha),
        num_steps=num_steps,
        batch_size=0,
        step_grad_factor=0,
        slot_start=1,
        epoch_metric="steps",
        fused_step=fused_step,
    )
    return Algorithm(meta=meta, init=lambda: ParamState(problem.x0),
                     step=step)


def gt_svrg_algorithm(problem: Problem, alpha: float, num_outer: int,
                      inner_steps: int, batch_size: int = 1) -> Algorithm:
    """GT-SVRG [paper refs 18/19]: SVRG estimator + gradient tracking; one
    gossip round per step (tracking replaces multi-consensus)."""
    inner = build_gt_svrg_inner_step(problem.loss_fn, problem.prox)
    full_grad_fn = build_node_full_grad_fn(problem.loss_fn, problem.full_data)

    def init():
        # standard GT init: tracker starts at the x0 full gradient (computed
        # once here, re-charged per outer round exactly like the host loops)
        est = svrg.SvrgState(snapshot=problem.x0,
                             full_grad=full_grad_fn(problem.x0))
        return GTSVRGState(params=problem.x0, anchor=problem.x0, est=est,
                           tracker=est.full_grad, v_prev=est.full_grad,
                           inner_sum=_zeros_like(problem.x0))

    def init_mix_state(state, make=compression.init_state):
        # one transport state per transmitted quantity: the step gossips
        # both the iterate and the tracking direction
        return state._replace(cstate=(make(problem.x0), make(problem.x0)))

    def outer(state):
        est = svrg.SvrgState(snapshot=state.anchor,
                             full_grad=full_grad_fn(state.anchor))
        return state._replace(est=est, inner_sum=_zeros_like(state.params))

    def make_step():
        def step(state, batch, phi, alpha):
            params, tracker, v_prev, cstate = inner(
                state.params, state.tracker, state.v_prev, state.est, batch,
                phi, alpha, state.cstate)
            return state._replace(
                params=params, tracker=tracker, v_prev=v_prev, cstate=cstate,
                inner_sum=svrg.tree_add(state.inner_sum, params))
        return step

    step = _shared_step(("gt_svrg_proto_step", inner), make_step)

    def end_outer(state, K):
        return state._replace(
            anchor=jax.tree.map(lambda acc: acc / K, state.inner_sum))

    meta = AlgoMeta(
        name="gt_svrg",
        stepsize=schedules.constant(alpha),
        outer_lengths=(inner_steps,) * num_outer,
        batch_size=batch_size,
        step_grad_factor=2,
        outer_full_grad=True,
        gossip_payloads=2,   # the step mixes the iterate AND the tracker
        record_key="global",
        final_record=False,
    )
    return Algorithm(meta=meta, init=init, step=step, outer=outer,
                     end_outer=end_outer, rule=DPSVRG_RULE,
                     init_mix_state=init_mix_state,
                     outer_traced=_svrg_outer_traced(problem.loss_fn),
                     end_outer_traced=_tail_average_end_outer_traced())


def loopless_dpsvrg_algorithm(problem: Problem, alpha: float, num_steps: int,
                              snapshot_prob: float = 0.05,
                              consensus_rounds: int = 2,
                              batch_size: int = 1) -> Algorithm:
    """BEYOND-PAPER: L-SVRG-style coin-flip snapshots — fixed-shape steps,
    no outer-loop bookkeeping (the variant the LM trainer approximates)."""
    inner = build_dpsvrg_inner_step(problem.loss_fn, problem.prox)
    full_grad_fn = build_node_full_grad_fn(problem.loss_fn, problem.full_data)

    def init():
        est = svrg.SvrgState(snapshot=problem.x0,
                             full_grad=full_grad_fn(problem.x0))
        return LooplessState(params=problem.x0, est=est)

    def init_mix_state(state, make=compression.init_state):
        return state._replace(cstate=make(problem.x0))

    def outer(state):
        return state._replace(est=svrg.SvrgState(
            snapshot=state.params, full_grad=full_grad_fn(state.params)))

    def make_step():
        def step(state, batch, phi, alpha):
            params, cstate = inner(state.params, state.est, batch, phi,
                                   alpha, state.cstate)
            return state._replace(params=params, cstate=cstate)
        return step

    step = _shared_step(("loopless_proto_step", inner), make_step)

    def fused_step(mode):
        finner = build_fused_svrg_inner(problem.loss_fn, problem.prox, mode)

        def make_fused():
            def fstep(state, batch, phi, alpha):
                params, cstate = finner(state.params, state.est, batch, phi,
                                        alpha, state.cstate)
                return state._replace(params=params, cstate=cstate)
            return fstep

        return _shared_step(("loopless_proto_fused", finner), make_fused)

    meta = AlgoMeta(
        name="loopless_dpsvrg",
        stepsize=schedules.constant(alpha),
        num_steps=num_steps,
        batch_size=batch_size,
        step_grad_factor=2,
        outer_full_grad=True,
        init_full_grad=True,
        gossip_rounds=lambda t: consensus_rounds,
        snapshot_prob=snapshot_prob,
        fused_step=fused_step,
    )
    return Algorithm(meta=meta, init=init, step=step, outer=outer,
                     rule=DPSVRG_RULE, init_mix_state=init_mix_state,
                     outer_traced=_loopless_outer_traced(problem.loss_fn))


def dvr_algorithm(problem: Problem, alpha: float, num_steps: int,
                  rho: float = 0.5, snapshot_prob: float = 0.05,
                  batch_size: int = 1) -> Algorithm:
    """Dual-Free DVR (Hendrikx et al., arXiv 2006.14384) — see
    :func:`build_dvr_inner_step` for the adaptation notes.  Flat loop with
    loopless coin-flip snapshot refreshes (DVR samples its full-gradient
    resyncs the same way); one gossip round per step with communication step
    size ``rho`` — the scenario matrix's non-gradient-tracking VR column."""
    inner = build_dvr_inner_step(problem.loss_fn, problem.prox, rho)
    full_grad_fn = build_node_full_grad_fn(problem.loss_fn, problem.full_data)

    def init():
        est = svrg.SvrgState(snapshot=problem.x0,
                             full_grad=full_grad_fn(problem.x0))
        return LooplessState(params=problem.x0, est=est)

    def init_mix_state(state, make=compression.init_state):
        return state._replace(cstate=make(problem.x0))

    def outer(state):
        return state._replace(est=svrg.SvrgState(
            snapshot=state.params, full_grad=full_grad_fn(state.params)))

    def make_step():
        def step(state, batch, phi, alpha):
            params, cstate = inner(state.params, state.est, batch, phi,
                                   alpha, state.cstate)
            return state._replace(params=params, cstate=cstate)
        return step

    step = _shared_step(("dvr_proto_step", inner), make_step)

    def fused_step(mode):
        finner = build_fused_svrg_inner(problem.loss_fn, problem.prox, mode,
                                        rho=rho)

        def make_fused():
            def fstep(state, batch, phi, alpha):
                params, cstate = finner(state.params, state.est, batch, phi,
                                        alpha, state.cstate)
                return state._replace(params=params, cstate=cstate)
            return fstep

        return _shared_step(("dvr_proto_fused", finner), make_fused)

    meta = AlgoMeta(
        name="dvr",
        stepsize=schedules.constant(alpha),
        num_steps=num_steps,
        batch_size=batch_size,
        step_grad_factor=2,
        outer_full_grad=True,
        init_full_grad=True,
        snapshot_prob=snapshot_prob,
        fused_step=fused_step,
    )
    return Algorithm(meta=meta, init=init, step=step, outer=outer,
                     rule=DPSVRG_RULE, init_mix_state=init_mix_state,
                     outer_traced=_loopless_outer_traced(problem.loss_fn))


ALGORITHMS: dict[str, Callable[..., Algorithm]] = {
    "dpsvrg": dpsvrg_algorithm,
    "dspg": dspg_algorithm,
    "dpg": dpg_algorithm,
    "gt_svrg": gt_svrg_algorithm,
    "loopless_dpsvrg": loopless_dpsvrg_algorithm,
    "dvr": dvr_algorithm,
}
