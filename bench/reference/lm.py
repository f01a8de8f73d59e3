"""Plain reference of decentralized LM training: the training rule, which
every architecture shares.

The model is a module of its own, ``bench/reference/<module>.py``, named by
the architecture module's ``reference`` (``bench/archs/<arch>.py``) and
handed to ``Run``.  It exports

* ``init_params(model, seed, precision)``: one node's parameter tree from
  ``seed``, in one jitted call, held in ``dtype(precision)``.  It draws its
  keys in the order the program's ``init_params`` draws them, so that with
  the program's initialisation rule both start from the same weights;
* ``loss_fn(params, tokens, labels, model)``: the mean next-token
  cross-entropy of one node's (B, L) rows, with the matmuls at whatever
  precision the caller sets.

``model`` is the configuration's ``"model"`` group.  The tree's leaf names
(``leaf_norms``) are the program's without the keys of the architecture's
``NESTING``.

The training rule is Algorithm 1 applied to the node-stacked parameters:

    snapshot refresh (every ``snapshot_every`` steps, DPSVRG only):
        snapshot <- x,   mu <- grad f_i(x; big batch)
    v_i   = grad f_i(x_i; b) - grad f_i(snap_i; b) + mu_i      (DPSVRG)
    v_i   = grad f_i(x_i; b)                                   (DSPG)
    q_i   = x_i - alpha v_i,   q_hat = Phi q,   x' = prox_{alpha h}(q_hat)

``precision`` is ``"highest"`` (float32 everywhere, every matmul at
``Precision.HIGHEST``) or ``"bfloat16"`` (the control: parameters, state and
activations held in bfloat16).  ``fault`` plants one fault in the rule for
the calibration of limits: ``"unchanged"`` (the step returns its state),
``"half_batch"`` (half the batch left out, the mean over the rest),
``"no_mix"`` (the exchange between nodes left out) or ``"answer"`` (the
recorded loss is that of the first half of each node's rows; the step
itself is sound).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import tokens as tok

GRAD_BLOCK = 4                  # sequences per gradient call
PRECISIONS = ("highest", "bfloat16")
FAULTS = (None, "unchanged", "half_batch", "no_mix", "answer")


def dtype(precision: str):
    """The type a model holds its parameters and activations in."""
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# the schedule, the prox
# ---------------------------------------------------------------------------

def mixing_product(m: int, b: int, rounds: int) -> np.ndarray:
    """Phi of ``rounds`` gossip rounds on the b-connected ring the
    configuration names.  With two nodes every slot of the ring is the one
    edge between them under Metropolis weights, i.e. plain averaging."""
    if m == 2:
        w = np.full((2, 2), 0.5)
    elif b == 1:
        w = np.eye(m) / 3.0
        for i in range(m):
            w[i, (i + 1) % m] = w[i, (i - 1) % m] = 1.0 / 3.0
    else:
        raise NotImplementedError("the reference knows the b-connected "
                                  "ring for m=2 or b=1 only")
    return np.linalg.matrix_power(w, rounds)


# ---------------------------------------------------------------------------
# the training rule
# ---------------------------------------------------------------------------

def leaf_norms(trees, skip=()) -> dict:
    """{leaf name: norm} over one tree or a list of per-node trees of like
    structure (the norm of the node-stacked leaf), in float32.  A leaf is
    named by its path of dict keys and list indices, without the keys in
    ``skip``."""
    trees = trees if isinstance(trees, list) else [trees]
    sums: dict = {}
    for tree in trees:
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            keys = [str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path]
            name = "/".join(k for k in keys if k not in skip)
            sq = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            sums[name] = sums.get(name, 0.0) + sq
    return {k: float(jnp.sqrt(v)) for k, v in sums.items()}


def _minus(a, b, scale=1.0):
    return jax.tree.map(lambda x, y: (x.astype(jnp.float32)
                                      - y.astype(jnp.float32)) * scale, a, b)


class Run:
    """The first steps of one training job, as the reference sees them."""

    def __init__(self, net, model: dict, job: dict, seeds: dict,
                 shards: np.ndarray, *, precision: str = "highest",
                 fault: str | None = None):
        """``net`` is the plain model module, ``model`` the sizes it is
        built at."""
        if precision not in PRECISIONS or fault not in FAULTS:
            raise ValueError(f"unknown precision {precision!r} or fault "
                             f"{fault!r}")
        self.net, self.model, self.job, self.seeds = net, model, job, seeds
        self.shards = shards
        self.precision, self.fault = precision, fault
        mp = "highest" if precision == "highest" else "default"

        def grad_fn(params, tokens, labels):
            with jax.default_matmul_precision(mp):
                return jax.value_and_grad(net.loss_fn)(params, tokens,
                                                       labels, model)

        self._vg = jax.jit(grad_fn)

    def _grad(self, params, tokens, labels):
        """Mean loss and gradient over (B, L) rows, in blocks of
        ``GRAD_BLOCK`` sequences (equal blocks, so the mean of means)."""
        if self.fault == "half_batch":
            tokens, labels = tokens[:len(tokens) // 2], labels[:len(labels) // 2]
        n = tokens.shape[0]
        blk = min(GRAD_BLOCK, n)
        if n % blk:
            raise ValueError(f"{n} rows do not split into blocks of {blk}")
        loss, grad = 0.0, None
        for s in range(0, n, blk):
            lv, g = self._vg(params, jnp.asarray(tokens[s:s + blk]),
                             jnp.asarray(labels[s:s + blk]))
            loss = loss + lv
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        nb = -(-n // blk)
        return loss / nb, jax.tree.map(lambda a: a / nb, grad)

    def answers(self, steps: int) -> dict:
        """The loss at each of the first ``steps`` steps, and the per-leaf
        norms of the first gradient as the optimizer gets it (DPSVRG: the
        snapshot's full gradient mu; DSPG: (x0 - x1) / alpha) and of the
        parameters' change x_steps - x0."""
        job, m = self.job, self.job["nodes"]
        vr = job["algorithm"] == "dpsvrg"
        seq, batch = job["seq_len"], job["per_node_batch"]
        snap_batch = batch * job["snapshot_batch_mult"]
        starts = tok.StartReplay(self.seeds["loader"], m,
                                 self.shards.shape[1], seq)
        x0 = self.net.init_params(self.model, self.seeds["init"],
                                  self.precision)
        x = [x0] * m
        snap, mu = list(x), [None] * m
        out = {"loss": []}
        for step in range(steps):
            if vr and step % job["snapshot_every"] == 0:
                st, sl = tok.gather_windows(self.shards,
                                            starts.draw(snap_batch), seq)
                for i in range(m):
                    mu[i] = self._grad(x[i], st[i], sl[i])[1]
                snap = list(x)
            bt, bl = tok.gather_windows(self.shards, starts.draw(batch), seq)
            alpha = job["alpha"] if vr else \
                job["alpha"] / math.sqrt(step + 1)
            losses, v = [], []
            for i in range(m):
                lv, g = self._grad(x[i], bt[i], bl[i])
                if vr:
                    gs = self._grad(snap[i], bt[i], bl[i])[1]
                    g = jax.tree.map(lambda a, b, c: a - b + c, g, gs, mu[i])
                losses.append(lv)
                v.append(g)
            if self.fault == "answer":
                half = batch // 2
                losses = [self._vg(x[i], jnp.asarray(bt[i][:half]),
                                   jnp.asarray(bl[i][:half]))[0]
                          for i in range(m)]
            out["loss"].append(float(sum(losses) / m))
            if self.fault != "unchanged":
                x = self._update(x, v, alpha, step)
            if step == 0:
                out["grad"] = leaf_norms(mu) if vr else leaf_norms(
                    [_minus(x0, xi, 1.0 / alpha) for xi in x])
            del v
        out["change"] = leaf_norms([_minus(xi, x0) for xi in x])
        return out

    def _update(self, x, v, alpha, step):
        job, m = self.job, self.job["nodes"]
        phi = (np.eye(m) if self.fault == "no_mix" else
               mixing_product(m, job["schedule_b"], job["consensus_rounds"]))
        lam = job["l1"]
        q = [jax.tree.map(lambda a, g: a - (alpha * g).astype(a.dtype),
                          x[i], v[i]) for i in range(m)]
        out = []
        for i in range(m):
            mixed = jax.tree.map(
                lambda *leaves: sum(
                    (float(phi[i, j]) * leaves[j].astype(jnp.float32)
                     for j in range(m) if phi[i, j] != 0.0),
                    jnp.zeros(leaves[0].shape, jnp.float32)), *q)
            t = alpha * lam
            out.append(jax.tree.map(
                lambda z, like: (jnp.sign(z) * jnp.maximum(jnp.abs(z) - t,
                                                           0.0)
                                 ).astype(like.dtype), mixed, x[i]))
        return out
