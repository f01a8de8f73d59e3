"""The names the program gives its work on the profiler's clock.

One flat vocabulary, read by the benchmark's trace reduction
(``bench/scopes.py``):

* device scopes (``scope(name)``, a ``jax.named_scope``): HLO metadata only,
  so they cost nothing at run time.  Each is placed once, in the shared
  function, so the resident, scan and host paths and the sweep executors
  all inherit it.  An op that sits under two of them belongs to the
  outermost (a gradient inside the snapshot refresh is snapshot time).
* host spans (``span(name, **counts)``, a ``jax.profiler.TraceAnnotation``):
  one per phase of a resident job, never one per step or per dispatched op.
  Each carries ``run=<n>`` (``next_run()``, one number per job, so every
  span of a job shares it) and the counts of its boundary.  Counts known
  only at the end of a phase go in with ``set_metadata`` on the span.
  Outside a trace a span costs about a microsecond.
"""

from __future__ import annotations

import itertools

import jax

__all__ = ["SAMPLE", "GRAD", "SNAPSHOT", "UPDATE", "MIX", "PROX",
           "FUSED_UPDATE", "RECORD", "SCOPES", "PLAN", "STAGE", "DISPATCH",
           "PULL", "CKPT", "SPANS", "scope", "span", "next_run"]

# device scopes
SAMPLE = "repro.sample"              # minibatch / token-window gathers
GRAD = "repro.grad"                  # a step's stochastic gradients
SNAPSHOT = "repro.snapshot"          # full-gradient refresh and its cond
UPDATE = "repro.update"              # direction arithmetic and x - alpha*v
MIX = "repro.mix"                    # gossip
PROX = "repro.prox"                  # the proximal map of a step
FUSED_UPDATE = "repro.fused_update"  # the fused Pallas mix + update + prox
RECORD = "repro.record"              # on-device objective and consensus
SCOPES = (SAMPLE, GRAD, SNAPSHOT, UPDATE, MIX, PROX, FUSED_UPDATE, RECORD)

# host spans
PLAN = "repro.plan"                  # args: steps, chunks
STAGE = "repro.stage"                # args: h2d_bytes, d2h_bytes,
#                                      h2d_buffers
DISPATCH = "repro.dispatch"          # args: dispatches
PULL = "repro.pull"                  # args: d2h_bytes
CKPT = "repro.ckpt"                  # args: d2h_bytes
SPANS = (PLAN, STAGE, DISPATCH, PULL, CKPT)

_RUNS = itertools.count(1)


def scope(name: str):
    """A device scope: ``with scope(GRAD): ...`` inside traced code."""
    return jax.named_scope(name)


def span(name: str, **counts):
    """A host span: ``with span(PLAN, run=run) as s: ...``."""
    return jax.profiler.TraceAnnotation(name, **counts)


def next_run() -> int:
    """A fresh job number for the ``run`` argument of a job's spans."""
    return next(_RUNS)
