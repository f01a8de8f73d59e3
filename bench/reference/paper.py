"""Plain reference of one DPSVRG job on the paper's l1-regularised logistic
regression (Algorithm 1, faithful multi-consensus).

Outer round s = 1..S has K_s = ceil(beta^s n0) inner steps.  At its start
the snapshot is the anchor and mu_i is node i's full local gradient there;
inner step k draws one row per node, takes

    v_i = grad f_i(x_i; a) - grad f_i(snap_i; a) + mu_i,
    q_i = x_i - alpha v_i,   q_hat = W^k q,   x' = soft(q_hat, alpha lam),

and adds x' to the round's sum; the round ends with anchor = sum / K_s.
Rows are drawn per step as ``integers(0, n, size=(m, batch))`` from one
seeded generator.  Records are taken at the start, after every step whose
in-round index is a multiple of ``record_every``, and after the last step:
the objective F(x_bar) = mean_i f_i(x_bar) + lam |x_bar|_1, the consensus
mean_i |x_i - x_bar|, and the effective epochs, cumulative gossip rounds
and steps.

``precision`` is ``"highest"`` (float32, every product at
``Precision.HIGHEST``) or ``"high"`` (the control: every product in three
bfloat16 passes, as ``Precision.HIGH`` computes on a TPU, written out so
that it computes the same on any backend).  ``fault`` plants one fault for
the calibration of limits: ``"unchanged"``, ``"half_batch"`` (the full
gradient over half of each node's rows), ``"no_mix"`` or ``"answer"`` (the
recorded objective without its regulariser).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")
FAULTS = (None, "unchanged", "half_batch", "no_mix", "answer")


def ring(m: int) -> np.ndarray:
    """The paper's ring (b = 1): each node averages itself and both
    neighbours with weight 1/3."""
    if m == 2:
        return np.full((2, 2), 0.5)
    w = np.eye(m) / 3.0
    for i in range(m):
        w[i, (i + 1) % m] = w[i, (i - 1) % m] = 1.0 / 3.0
    return w


def _einsum(spec, a, b, precision):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    f = lambda x, y: jnp.einsum(spec, x, y,
                                preferred_element_type=jnp.float32)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def plan(job: dict, m: int, n: int, seed: int) -> dict:
    """Every data-independent input of the job, step by step."""
    rng = np.random.default_rng(seed)
    lengths = [int(math.ceil(job["beta"] ** s * job["n0"]))
               for s in range(1, job["num_outer"] + 1)]
    w = ring(m)
    every, bsz = job["record_every"], job["batch"]
    cols = {"epochs": [0.0], "comm_rounds": [0], "steps": [0]}
    xs = {k: [] for k in ("idx", "phi", "o_pre", "e_post", "K", "rec")}
    grad_evals, comm, t = 0, 0, 0
    recorded = True
    for K in lengths:
        grad_evals += m * n
        for k in range(1, K + 1):
            xs["idx"].append(rng.integers(0, n, size=(m, bsz)))
            xs["phi"].append(np.linalg.matrix_power(w, k))
            xs["o_pre"].append(k == 1)
            xs["e_post"].append(k == K)
            xs["K"].append(float(K))
            comm += k
            t += 1
            grad_evals += 2 * m * bsz
            recorded = k % every == 0
            xs["rec"].append(recorded)
            if recorded:
                cols["epochs"].append(grad_evals / float(m * n))
                cols["comm_rounds"].append(comm)
                cols["steps"].append(t)
    if not recorded:
        xs["rec"][-1] = True
        cols["epochs"].append(grad_evals / float(m * n))
        cols["comm_rounds"].append(comm)
        cols["steps"].append(t)
    arrays = {"idx": np.stack(xs["idx"]).astype(np.int32),
              "phi": np.stack(xs["phi"]).astype(np.float32),
              "o_pre": np.asarray(xs["o_pre"]),
              "e_post": np.asarray(xs["e_post"]),
              "K": np.asarray(xs["K"], np.float32),
              "rec": np.asarray(xs["rec"])}
    return {"xs": arrays, "cols": {k: np.asarray(v) for k, v in cols.items()},
            "steps": t}


@functools.lru_cache(maxsize=None)
def _job_fn(m, alpha, lam, precision, fault):
    def loss_rows(w, feats, labels):          # w (m, d) -> (m,) mean loss
        z = _einsum("mnd,md->mn", feats, w, precision)
        return jnp.mean(-labels * z + jnp.logaddexp(0.0, z), axis=1)

    def grad_rows(w, feats, labels):          # (m, d) mean gradient
        z = _einsum("mnd,md->mn", feats, w, precision)
        r = (jax.nn.sigmoid(z) - labels) / feats.shape[1]
        return _einsum("mnd,mn->md", feats, r, precision)

    def record(x, feats, labels):
        xbar = jnp.mean(x, axis=0)
        f = jnp.mean(loss_rows(jnp.broadcast_to(xbar, x.shape), feats,
                               labels))
        if fault != "answer":
            f = f + lam * jnp.sum(jnp.abs(xbar))
        cons = jnp.mean(jnp.linalg.norm(x - xbar, axis=1))
        return f, cons

    def job(feats, labels, xs, n_records):
        half = feats.shape[1] // 2
        full_f = feats[:, :half] if fault == "half_batch" else feats
        full_l = labels[:, :half] if fault == "half_batch" else labels
        x0 = jnp.zeros((m, feats.shape[2]), jnp.float32)
        obj = jnp.zeros(n_records, jnp.float32)
        cons = jnp.zeros(n_records, jnp.float32)
        f0, c0 = record(x0, feats, labels)
        obj, cons = obj.at[0].set(f0), cons.at[0].set(c0)

        def body(c, s):
            x, anchor, snap, mu, acc, obj, cons, slot = c
            snap, mu, acc = jax.lax.cond(
                s["o_pre"],
                lambda: (anchor, grad_rows(anchor, full_f, full_l),
                         jnp.zeros_like(acc)),
                lambda: (snap, mu, acc))
            a = jnp.take_along_axis(feats, s["idx"][:, :, None], axis=1)
            y = jnp.take_along_axis(labels, s["idx"], axis=1)
            v = grad_rows(x, a, y) - grad_rows(snap, a, y) + mu
            q = x - alpha * v
            phi = jnp.eye(m) if fault == "no_mix" else s["phi"]
            qh = _einsum("ij,jd->id", phi, q, precision)
            new = jnp.sign(qh) * jnp.maximum(jnp.abs(qh) - alpha * lam, 0.0)
            x = x if fault == "unchanged" else new
            acc = acc + x
            anchor = jnp.where(s["e_post"], acc / s["K"], anchor)

            def write(oc):
                o, cc, sl = oc
                f, cn = record(x, feats, labels)
                return o.at[sl].set(f), cc.at[sl].set(cn), sl + 1

            obj, cons, slot = jax.lax.cond(s["rec"], write, lambda oc: oc,
                                           (obj, cons, slot))
            return (x, anchor, snap, mu, acc, obj, cons, slot), None

        zero = jnp.zeros_like(x0)
        carry = (x0, x0, x0, zero, zero, obj, cons, jnp.int32(1))
        carry, _ = jax.lax.scan(body, carry, xs)
        return carry[0], carry[5], carry[6]

    return jax.jit(job, static_argnums=3)


def run_job(data: dict, problem: dict, job: dict, seed: int, *,
            precision: str = "highest", fault: str | None = None) -> dict:
    """The reference's history and final iterate for one job."""
    if precision not in PRECISIONS or fault not in FAULTS:
        raise ValueError(f"unknown precision {precision!r} or fault "
                         f"{fault!r}")
    feats = jnp.asarray(data["features"])
    labels = jnp.asarray(data["labels"])
    m, n = labels.shape
    p = plan(job, m, n, seed)
    fn = _job_fn(m, job["alpha"], problem["l1"], precision, fault)
    x, obj, cons = fn(feats, labels, p["xs"], len(p["cols"]["steps"]))
    out = {k: np.asarray(v) for k, v in p["cols"].items()}
    out.update(objective=np.asarray(obj, np.float64),
               consensus=np.asarray(cons, np.float64),
               params=np.asarray(x, np.float64))
    return out
