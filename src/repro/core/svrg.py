"""SVRG variance-reduced gradient estimation (paper Section III-A).

The estimator at inner step (k, s):

    v_i = grad_B f_i(x_i)  -  grad_B f_i(x_tilde_i)  +  full_grad_i(x_tilde_i)

where ``x_tilde_i`` is the outer-loop snapshot and ``full_grad_i`` is the
full local gradient recomputed once per outer round.  ``v_i`` is unbiased for
``grad f_i(x_i)`` and its variance vanishes as both points approach the
optimum (paper Lemma 7).

This module is deliberately model-agnostic: it consumes a ``grad_fn`` of
signature ``grad_fn(params, batch) -> pytree`` and handles the snapshot state
bookkeeping.  It works both for single-node (plain pytrees) and stacked
decentralized parameters (leading node axis), because all operations are
leaf-wise arithmetic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import spans

__all__ = ["SvrgState", "init_snapshot", "corrected_gradient", "tree_sub",
           "tree_add", "tree_axpy", "tree_dot", "tree_norm"]


class SvrgState(NamedTuple):
    """Outer-loop snapshot state.

    snapshot:  x_tilde (same structure as params)
    full_grad: grad f(x_tilde) over the full local dataset (mu in SVRG papers)
    """
    snapshot: Any
    full_grad: Any


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_axpy(alpha, x, y):
    """y + alpha * x, leaf-wise."""
    return jax.tree.map(lambda xi, yi: yi + alpha * xi, x, y)


def tree_dot(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return sum(jnp.vdot(x, y) for x, y in zip(leaves_a, leaves_b))


def tree_norm(a):
    return jnp.sqrt(tree_dot(a, a).real)


def init_snapshot(params, full_grad_fn: Callable) -> SvrgState:
    """Take a snapshot at ``params`` and compute the full local gradient.

    ``full_grad_fn(params) -> pytree`` must already average over the node's
    whole local dataset (for stacked params: vmapped over the node axis).
    """
    return SvrgState(snapshot=params, full_grad=full_grad_fn(params))


def corrected_gradient(grad_fn: Callable, params, state: SvrgState, batch):
    """The SVRG estimator v = g(x; B) - g(x_tilde; B) + mu.

    ``grad_fn(params, batch)`` evaluates the minibatch gradient; it is called
    twice on the *same* batch (at the iterate and at the snapshot) so the two
    stochastic terms are maximally correlated — the variance-reduction
    mechanism described in the paper ("Why does the correction work?").
    """
    g_now = grad_fn(params, batch)
    g_snap = grad_fn(state.snapshot, batch)
    with spans.scope(spans.UPDATE):
        return jax.tree.map(lambda a, b, mu: a - b + mu,
                            g_now, g_snap, state.full_grad)
