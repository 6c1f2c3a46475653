"""Plain reference for a factorization machine trained by full-batch L-BFGS.

Imports nothing of the program. float32 `jax.numpy` at `highest` matmul
precision, rows in blocks so that it fits. It follows the published
description (Rendle's O(nk) form; ytk-learn's HoagOptimizer for the outer
loop): weighted-sum logistic loss, L2 scaled by the total weight, first step
1/||g||, the two-loop recursion over the last m pairs, a backtracking line
search that accepts on sufficient decrease and the Wolfe curvature condition
(shrink by `step_decr` while the decrease fails, else grow by `step_incr`).

`compute` is the precision the loss+gradient pass runs in: float32 for the
reference, bfloat16 for the control put in the program's place.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def make_pass(nf: int, k: int, need_bias: bool, bias_latent: bool, block: int,
              compute=jnp.float32):
    """loss_and_grad(w, idx, val, y, wt) over all rows, a block at a time."""

    def block_loss(w, idx, val, y, wt):
        w = w.astype(compute)
        val_c = val.astype(compute)
        w1 = w[:nf]
        Vt = w[nf:].reshape(nf, k).T  # (k, nf)
        if need_bias and not bias_latent:
            Vt = Vt.at[:, 0].set(0.0)
        wx = jnp.sum(val_c * w1[idx], axis=-1)
        vx = Vt[:, idx] * val_c[None]  # (k, b, width)
        S = jnp.sum(vx, axis=-1)
        S2 = jnp.sum(vx * vx, axis=-1)
        s = (wx + 0.5 * jnp.sum(S * S - S2, axis=0)).astype(jnp.float32)
        per = jnp.log1p(jnp.exp(-jnp.abs(s))) + jnp.maximum(s, 0.0) - s * y
        return jnp.sum(wt * per)

    vg = jax.jit(jax.value_and_grad(block_loss))

    def loss_and_grad(w, idx, val, y, wt):
        n = idx.shape[0]
        loss = jnp.zeros((), jnp.float32)
        grad = jnp.zeros_like(w)
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            l, g = vg(w, idx[lo:hi], val[lo:hi], y[lo:hi], wt[lo:hi])
            loss, grad = loss + l, grad + g.astype(jnp.float32)
        return loss, grad

    return loss_and_grad


def two_loop(g, hist):
    """-H^-1 g over the stored (s, y, ys) pairs, newest last."""
    p = -g
    alphas = []
    for s, y, ys in reversed(hist):
        a = jnp.vdot(s, p) / ys
        p = p - a * y
        alphas.append(a)
    s, y, ys = hist[-1]
    p = p * ys / jnp.vdot(y, y)
    for (s, y, ys), a in zip(hist, reversed(alphas)):
        b = jnp.vdot(y, p) / ys
        p = p + (a - b) * s
    return p


def follow(pass_fn, w0, batch, l2_vec, g_weight: float, n_iter: int, ls: dict,
           m: int = 8) -> dict:
    """First evaluation and `n_iter` L-BFGS iterations. Returns the loss at
    each point, the first gradient, the weights after the last iteration and
    the line-search trials of each iteration."""
    with jax.default_matmul_precision("highest"):
        def full(w):
            pure, g = pass_fn(w, *batch)
            loss = pure + 0.5 * g_weight * jnp.sum(l2_vec * w * w)
            return loss, g + g_weight * l2_vec * w

        w = jnp.asarray(w0, jnp.float32)
        loss, g = full(w)
        out = {"loss": [float(loss)], "g0": g, "trials": [], "passes": 1}
        step = 1.0 / max(float(jnp.linalg.norm(g)), 1e-300)
        hist = []
        for _ in range(n_iter):
            p = two_loop(g, hist) if hist else -g
            dginit = float(jnp.vdot(g, p))
            trials = 0
            while True:
                w_try = w + step * p
                loss_t, g_t = full(w_try)
                trials += 1
                out["passes"] += 1
                suff = float(loss_t) <= float(loss) + ls["c1"] * float(jnp.vdot(w_try - w, g))
                wolfe = float(jnp.vdot(p, g_t)) >= ls["c2"] * dginit
                if suff and wolfe:
                    break
                if trials >= ls["max_iter"]:
                    raise RuntimeError("reference line search did not end")
                step *= ls["step_decr"] if not suff else ls["step_incr"]
            s, y = w_try - w, g_t - g
            ys = jnp.vdot(y, s)
            ys = jnp.where(ys < 1e-60, 0.01 * jnp.vdot(y, y), ys)
            hist = (hist + [(s, y, ys)])[-m:]
            w, g, loss, step = w_try, g_t, loss_t, 1.0
            out["loss"].append(float(loss))
            out["trials"].append(trials)
        out["w"] = w
        return out


def leaf_norms(vec, nf: int) -> np.ndarray:
    """Norms of the two leaves: bias and first-order weights, latent table."""
    v = jnp.asarray(vec, jnp.float32)
    return np.array([float(jnp.linalg.norm(v[:nf])), float(jnp.linalg.norm(v[nf:]))])


def norm_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf: the gap between the two norms against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / np.maximum(floor, 1e-300)))


def gaps(prog: dict, ref: dict, nf: int) -> dict:
    """prog/ref: {"loss": [...], "g0", "w0", "w"} at the same iteration."""
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    n = min(len(lp), len(lr))
    return {
        "loss_gap": float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n]))),
        "grad_gap": norm_gap(leaf_norms(prog["g0"], nf), leaf_norms(ref["g0"], nf)),
        "dw_gap": norm_gap(leaf_norms(jnp.asarray(prog["w"]) - jnp.asarray(prog["w0"]), nf),
                           leaf_norms(jnp.asarray(ref["w"]) - jnp.asarray(ref["w0"]), nf)),
    }
