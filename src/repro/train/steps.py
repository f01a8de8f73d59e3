"""Jitted step builders: decentralized training (DPSVRG / DSPG), conventional
all-reduce baselines, and serving (prefill / decode).

This is where the paper's algorithm becomes the framework's data-parallel
training rule for every architecture in the zoo:

  * parameters are *stacked* per node (leading axis m) and sharded over the
    mesh's ``node_axes``; the per-node loss/grad is a ``jax.vmap`` over that
    axis (GSPMD keeps it communication-free),
  * the SVRG correction uses the per-node snapshot + large-batch "full"
    gradient state,
  * gossip is the host-precomputed multi-consensus matrix applied as one
    einsum (one cross-node collective per step),
  * the prox step is the regularizer's closed form (or the fused Pallas
    kernel on TPU — see repro.kernels.fused_update).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import algorithm as algo_lib, compression, gossip, \
    prox as prox_lib, spans, svrg
from repro.models import transformer
from repro.models.api import ModelConfig
from . import sharding

__all__ = ["TrainBundle", "ServeBundle", "build_train_step",
           "build_serve_steps", "make_stacked_init", "TrainState"]


class TrainState(NamedTuple):
    params: Any            # stacked (m, ...)
    snapshot: Any          # stacked (m, ...)
    full_grad: Any         # stacked (m, ...)
    step: jax.Array
    # transport state for stateful gossip (compressed error feedback,
    # scenario delay FIFOs) — None for stateless wire formats, so legacy
    # 4-field construction sites and checkpoints keep working unchanged
    mix_state: Any = None


class TrainBundle(NamedTuple):
    train_step: Callable   # (state, batch, phi, alpha) -> (state, metrics)
    snapshot_step: Callable  # (state, big_batch) -> state
    init_state: Callable   # (rng) -> state
    state_shardings: Any
    batch_shardings: Callable  # batch pytree -> shardings
    loss_fn: Callable


class ServeBundle(NamedTuple):
    prefill_step: Callable
    decode_step: Callable
    init_params: Callable
    param_shardings: Any
    cache_shardings: Callable


def _named(mesh, spec_tree):
    if mesh is None:
        return None
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Decentralized training
# ---------------------------------------------------------------------------

def make_stacked_init(cfg: ModelConfig, m: int):
    """All nodes start from the same point (Algorithm 1 line 2)."""

    def init(rng):
        params = transformer.init_params(cfg, rng)
        return gossip.stack_tree(params, m)

    return init


# Rebuilt bundles with identical (cfg, prox, m, rule) are served from this
# cache so their jitted step identities stay stable across train_loop calls
# — what lets the trainer's compiled chunk executors (and jax.jit's own
# cache) persist across runs, the same property algorithm._shared_step
# gives the repro-scale runner.  Keyed on the frozen-dataclass equality of
# cfg/prox (reusing a prox INSTANCE hits; rebuilding one recompiles, which
# is merely slow, never wrong).
_BUNDLE_CACHE: "collections.OrderedDict[tuple, TrainBundle]" = \
    collections.OrderedDict()
_BUNDLE_CACHE_MAX = 16


def build_train_step(cfg: ModelConfig,
                     prox: prox_lib.Prox,
                     m: int,
                     plan: sharding.MeshPlan | None = None,
                     mesh=None,
                     algorithm: str | algo_lib.UpdateRule = "dpsvrg",
                     donate: bool = True) -> TrainBundle:
    """``algorithm``: an ``UpdateRule`` from ``repro.core.algorithm`` (or its
    registry name: dpsvrg | dspg).  The LM train step is the SAME prox-gossip
    update the repro-scale runner executes — ``algo_lib.prox_gossip_update``
    with the rule's gradient direction — so decentralized LM training and the
    paper reproduction cannot drift apart.

    The train step's ``phi`` argument is any stateless transport wire format
    (``gossip.mix_stacked`` dispatches on its type): a dense ``(m, m)``
    matrix (paper-faithful baseline lowering; GSPMD all-gathers all m
    copies), a ``gossip.BandedPhi`` (cyclic-band gossip), or a
    ``gossip.PermutePhi`` (bands as ``lax.ppermute`` collectives on a
    node-axis mesh) — numerically identical, O(degree) instead of O(m)
    communication for band-structured schedules.  Build phis with a
    ``repro.core.transport`` backend (see ``trainer.train_loop``)."""
    rule = (algo_lib.UPDATE_RULES[algorithm] if isinstance(algorithm, str)
            else algorithm)
    cache_key = None
    if plan is None and mesh is None:
        try:
            cache_key = (cfg, prox, m, rule, donate)
            cached = _BUNDLE_CACHE.get(cache_key)
        except TypeError:            # unhashable custom cfg/prox: just build
            cache_key, cached = None, None
        if cached is not None:
            _BUNDLE_CACHE.move_to_end(cache_key)
            return cached
    bundle = _build_train_step(cfg, prox, m, plan, mesh, rule, donate)
    if cache_key is not None:
        _BUNDLE_CACHE[cache_key] = bundle
        while len(_BUNDLE_CACHE) > _BUNDLE_CACHE_MAX:
            _BUNDLE_CACHE.popitem(last=False)
    return bundle


def _build_train_step(cfg, prox, m, plan, mesh, rule,
                      donate) -> TrainBundle:
    loss = transformer.loss_fn(cfg)
    vgrad = jax.vmap(jax.value_and_grad(loss))
    grad_only = jax.vmap(jax.grad(loss))

    def train_step(state: TrainState, batch, phi, alpha):
        with spans.scope(spans.GRAD):
            losses, g_now = vgrad(state.params, batch)
            g_snap = grad_only(state.snapshot, batch) \
                if rule.needs_snapshot else None
        with spans.scope(spans.UPDATE):
            v = rule.direction(g_now, g_snap, state.full_grad)
            # XLA fuses the norm's reductions with the update's tree
            # arithmetic; the fusion takes the name of its root, the norm
            v_norm = svrg.tree_norm(v)

        # the mix threads the transport state (compressed error feedback,
        # scenario delay FIFOs) via the dispatching mix_with_state; for
        # stateless wire formats it degenerates to gossip.mix_stacked and
        # the state (None) passes through untouched
        mix_out = {}

        def mix_fn(phi_, tree):
            mixed, mix_out["state"] = compression.mix_with_state(
                phi_, tree, state.mix_state)
            return mixed

        new_params = algo_lib.prox_gossip_update(state.params, v, phi, alpha,
                                                 prox, mix_fn=mix_fn)
        metrics = {
            "loss": jnp.mean(losses),
            "v_norm": v_norm,
        }
        return state._replace(params=new_params, step=state.step + 1,
                              mix_state=mix_out["state"]), metrics

    def snapshot_step(state: TrainState, big_batch):
        """Outer loop: refresh snapshot + (large-batch) full local gradient."""
        with spans.scope(spans.SNAPSHOT):
            mu = grad_only(state.params, big_batch)
        return state._replace(snapshot=state.params, full_grad=mu)

    def init_state(rng):
        params = make_stacked_init(cfg, m)(rng)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return TrainState(params=params, snapshot=params, full_grad=zeros,
                          step=jnp.zeros((), jnp.int32))

    state_shardings = None
    batch_shardings = lambda batch: None
    if mesh is not None and plan is not None:
        axis_sizes = dict(mesh.shape)
        pspecs = sharding.param_specs(
            jax.eval_shape(init_state, jax.random.PRNGKey(0)).params,
            plan, stacked=True, axis_sizes=axis_sizes)
        state_spec = TrainState(params=pspecs, snapshot=pspecs,
                                full_grad=pspecs, step=P())
        state_shardings = _named(mesh, state_spec)

        def batch_shardings(batch):
            return jax.tree.map(
                lambda leaf: NamedSharding(
                    mesh, sharding.batch_spec(plan, np.ndim(leaf),
                                              shape=np.shape(leaf),
                                              axis_sizes=axis_sizes)), batch)

        train_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, None, None, None),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else ())
        snapshot_step = jax.jit(
            snapshot_step,
            in_shardings=(state_shardings, None),
            out_shardings=state_shardings,
            donate_argnums=(0,) if donate else ())
    else:
        train_step = jax.jit(train_step)
        snapshot_step = jax.jit(snapshot_step)

    return TrainBundle(train_step=train_step, snapshot_step=snapshot_step,
                       init_state=init_state, state_shardings=state_shardings,
                       batch_shardings=batch_shardings, loss_fn=loss)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def build_serve_steps(cfg: ModelConfig,
                      plan: sharding.MeshPlan | None = None,
                      mesh=None) -> ServeBundle:
    def prefill_step(params, tokens, image_embeds=None, audio_frames=None,
                     max_len=None):
        return transformer.prefill(cfg, params, tokens,
                                   image_embeds=image_embeds,
                                   audio_frames=audio_frames, max_len=max_len)

    def decode_step(params, cache, token):
        return transformer.decode_step(cfg, params, cache, token)

    param_shardings = None
    cache_shardings = lambda cache: None
    if mesh is not None and plan is not None:
        axis_sizes = dict(mesh.shape)
        pshape = jax.eval_shape(
            lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
        pspecs = sharding.param_specs(pshape, plan, stacked=False,
                                      axis_sizes=axis_sizes)
        param_shardings = _named(mesh, pspecs)

        def cache_shardings(cache):
            specs = sharding.cache_specs(cache, plan, axis_sizes=axis_sizes)
            return _named(mesh, specs)

    return ServeBundle(prefill_step=prefill_step, decode_step=decode_step,
                       init_params=lambda rng: transformer.init_params(cfg, rng),
                       param_shardings=param_shardings,
                       cache_shardings=cache_shardings)
