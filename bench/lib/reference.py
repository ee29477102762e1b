"""The plain reference's first three training steps, and the comparison
that decides ``correct``.

``train`` runs a configuration's reference (``layout`` and ``row_loss`` of
``bench/configs/<config>.py``) in float32 from the seed's weights through
three steps of the cell's optimizer: mean token cross-entropy over the
batch, the gradient clipped to a global norm, Adam with a linear warmup.
Weights are held as a float32 master; each forward pass reads them rounded
to the dtype the layout stores them in, as the program does, and the
gradient passes that rounding straight through.  Rows are processed one
at a time so that the reference fits one chip at the timed sizes.

It returns each step's loss, the per-leaf norms of the first step's
clipped gradient and of the master's change over the three steps.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights

F32 = jnp.float32


def _lr(opt: dict, step: int) -> float:
    return opt["lr"] * min(step / max(opt["warmup"], 1), 1.0)


def train(ref, c: dict, opt: dict, seed: int, batches: list,
          prec: str = "f32", grad_rows=None, loss_rows=None) -> dict:
    """See the module docstring.  Every product the reference traces,
    also those XLA makes of a cumulative sum, is at ``HIGHEST``
    precision."""
    with jax.default_matmul_precision("highest"):
        return _train(ref, c, opt, seed, batches, prec, grad_rows,
                      loss_rows)


def _train(ref, c, opt, seed, batches, prec, grad_rows, loss_rows) -> dict:
    """Three reference steps on ``batches`` (numpy token/label dicts).

    ``grad_rows`` / ``loss_rows`` (row ranges) restrict the gradient and
    the loss to some rows, the mean taken over those: a fault planted in
    the reference put in the program's place (half the batch left
    out)."""
    layout = ref.layout(c)
    dtypes = {e[0]: jnp.dtype(e[2]) for e in layout}
    master = {p: a.astype(F32) for p, a in weights.make(seed, layout).items()}

    def view(ms):
        # forward reads the stored dtype; the gradient is that of the f32
        return {p: ms[p] + jax.lax.stop_gradient(
            ms[p].astype(dtypes[p]).astype(F32) - ms[p]) for p in ms}

    def row_loss(ms, tok, lab):
        return ref.row_loss(view(ms), tok, lab, c, prec)

    @jax.jit
    def row_value(ms, tok, lab):
        return row_loss(ms, tok, lab)

    def row_grad_acc(ms, acc, tok, lab):
        g = jax.grad(row_loss)(ms, tok, lab)
        return jax.tree.map(jnp.add, acc, g)

    def row_both_acc(ms, acc, tok, lab):
        val, g = jax.value_and_grad(row_loss)(ms, tok, lab)
        return val, jax.tree.map(jnp.add, acc, g)

    row_grad = jax.jit(row_grad_acc, donate_argnums=(1,))
    row_both = jax.jit(row_both_acc, donate_argnums=(1,))

    @jax.jit
    def adam(ms, m, v, g, step, lr):
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        t = step + 1.0
        out_m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        out_v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

        def upd(w, a, b):
            u = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
            if opt["weight_decay"]:
                u = u + opt["weight_decay"] * w
            return w - lr * u
        return jax.tree.map(upd, ms, out_m, out_v), out_m, out_v

    @jax.jit
    def clip(g, n_tok):
        g = jax.tree.map(lambda a: a / n_tok, g)
        gn = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        s = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
        return jax.tree.map(lambda a: a * s, g)

    norms = jax.jit(lambda t: {p: jnp.sqrt(jnp.sum(a * a))
                               for p, a in t.items()})
    dnorms = jax.jit(lambda a, b: {p: jnp.sqrt(jnp.sum((a[p] - b[p]) ** 2))
                                   for p in a})

    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(master), zeros(master)
    losses, grad0 = [], None
    for step, b in enumerate(batches):
        n_rows, seq = b["tokens"].shape
        lrows = range(n_rows) if loss_rows is None else loss_rows
        grows = range(n_rows) if grad_rows is None else grad_rows
        total = jnp.float32(0.0)
        acc = zeros(master)
        if lrows == grows:              # one pass gives the loss and gradient
            for r in grows:
                val, acc = row_both(master, acc, b["tokens"][r],
                                    b["labels"][r])
                total = total + val
        else:
            for r in lrows:
                total = total + row_value(master, b["tokens"][r],
                                          b["labels"][r])
            for r in grows:
                acc = row_grad(master, acc, b["tokens"][r], b["labels"][r])
        losses.append(float(total) / (len(lrows) * seq))
        g = clip(acc, jnp.float32(len(grows) * seq))
        del acc
        if step == 0:
            grad0 = {p: float(x) for p, x in norms(g).items()}
            grad_vec = {p: np.asarray(x) for p, x in g.items()}
        master, m, v = adam(master, m, v, g, jnp.float32(step),
                            jnp.float32(_lr(opt, step)))
        del g
    del m, v
    master0 = {p: a.astype(F32) for p, a in weights.make(seed, layout).items()}
    delta = {p: float(x) for p, x in dnorms(master, master0).items()}
    return {"loss": losses, "grad": grad0, "delta": delta,
            "grad_vec": grad_vec}


# --------------------------------------------------------------------------
# the comparison
# --------------------------------------------------------------------------

def _worst_leaf(prog: dict, ref: dict, keep, den=None) -> tuple:
    """Largest |prog - ref| of a leaf, over max(the reference's norm of
    that leaf, of the median leaf); returns (gap, leaf).  ``den`` gives
    those norms where ``ref`` does not hold them."""
    den_of = ref if den is None else den
    med = statistics.median(den_of[p] for p in keep)
    worst, at = 0.0, None
    for p in keep:
        den = max(den_of[p], med)
        gap = abs(prog[p] - ref[p]) / den if den > 0 else math.inf
        if at is None or gap > worst:
            worst, at = gap, p
    return worst, at


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, each by the worst leaf where
    it is per leaf (over the larger of the reference's norm of that leaf
    and of the median leaf):

    loss_gap   largest |loss - ref| / ref over the first two steps, which
               run at the seed's weights (the warmup's learning rate is 0
               at the first step)
    grad_gap   gap of the first step's clipped gradient norm
    grad_err   norm of the first step's clipped gradient minus the
               reference's: first order in rounding, where the gaps of
               norms and of means are second order
    delta_gap  gap of the norm of the master's change over three steps,
               over the leaves whose reference gradient is at least a
               thousandth of the median leaf's (a leaf whose gradient is
               nought to rounding moves under Adam by round-off alone)

    ``loss3_gap``, the third step's loss after the first update, is
    reported and not compared (see PERF.md).
    """
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference hold different leaves: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(prog["loss"], ref["loss"])]
    if any(not math.isfinite(x) for x in prog["loss"]):
        loss_gaps = [math.inf] * len(loss_gaps)
    loss_gap = max(loss_gaps[:2])
    leaves = sorted(ref["grad"])
    grad_gap, grad_leaf = _worst_leaf(prog["grad"], ref["grad"], leaves)
    med = statistics.median(ref["grad"][p] for p in leaves)
    moving = [p for p in leaves if ref["grad"][p] >= 1e-3 * med]
    delta_gap, delta_leaf = _worst_leaf(prog["delta"], ref["delta"], moving)
    err = {p: float(np.linalg.norm((prog["grad_vec"][p].astype(np.float64)
                                    - ref["grad_vec"][p]).ravel()))
           for p in leaves}
    grad_err, err_leaf = _worst_leaf(err, {p: 0.0 for p in leaves}, leaves,
                                     den=ref["grad"])
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap, "grad_err": grad_err,
            "loss3_gap": loss_gaps[2], "loss_gaps": loss_gaps, "grad_leaf": grad_leaf,
            "delta_leaf": delta_leaf, "err_leaf": err_leaf,
            "left_out": sorted(set(leaves) - set(moving))}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits) and all(
        math.isfinite(numbers[k]) for k in limits)


def batches_np(corpus, n: int = 3) -> list:
    return [corpus.batch_at(i) for i in range(n)]
