"""Plain reference for ytk-learn's gradient-boosted soft trees (gbmlr, gbsdt,
gbhmlr, gbhsdt): one tree fitted by full-batch L-BFGS against the score the
earlier trees left, then folded into that score.

Imports nothing of the program. float32 `jax.numpy` at `highest` matmul
precision, rows in blocks so that it fits. From the published equations
(ytk-learn's GBMLRHoagOptimizer / GBSDTHoagOptimizer and their hierarchical
twins, GBMLRDataFlow for the fold). A row has slots j with feature f_j and
value x_j; a feature f has K - 1 gate weights g[f, :] and, in the mlr
variants, K expert weights u[f, :]; m_f in {0, 1} is the tree's feature mask:

    gate logits   a_p = sum_j m_{f_j} x_j g[f_j, p]            p < K - 1
    leaf probs    flat:  pi = softmax([a_0 .. a_{K-2}, 0])
                  heap:  the K - 1 logits are the inner nodes of a complete
                         binary tree in heap order (node i: children 2i + 1,
                         2i + 2; leaf p is node K - 1 + p); a node sends a row
                         left with probability sigmoid(a_i), and pi_p is the
                         product along leaf p's path from the root
    experts       mlr:  e_p = sum_j x_j u[f_j, p]     sdt:  e_p = leaf_p
    tree output   t = sum_p pi_p e_p
    score         s = z + t                    (z: what earlier trees left)
    loss          sum_r wt_r (log(1 + exp(s_r)) - y_r s_r)

The flat vector is, per feature, [g (K - 1), u (K)] (mlr: n_features rows of
2K - 1) or [leaves (K)] ++ [g (n_features, K - 1)] (sdt). L2 lies on all but
the bias feature's block (sdt: on the leaves too), scaled by the total
weight. Loss and gradient are summed over rows in float32 in two levels: a
block's rows among themselves, then the blocks' sums.

The outer loop (first step 1/||g||, two-loop recursion, backtracking Wolfe
line search) is `fm_ref.follow`, loaded by its path. The fold is
`z <- z + lr * t(w)` with the fitted w.

`compute` is the precision the loss+gradient pass runs in: float32 for the
reference, bfloat16 for the control put in the program's place.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "perfbench_reference_fm_ref",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fm_ref.py"))
_fm_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fm_ref)
follow, norm_gap = _fm_ref.follow, _fm_ref.norm_gap

VARIANTS = ("gbmlr", "gbsdt", "gbhmlr", "gbhsdt")


def scalar_leaves(variant: str) -> bool:
    return variant in ("gbsdt", "gbhsdt")


def dim(variant: str, nf: int, K: int) -> int:
    return K + nf * (K - 1) if scalar_leaves(variant) else nf * (2 * K - 1)


def split(variant: str, w, nf: int, K: int):
    """(gates (nf, K-1), experts): experts (nf, K) or the K leaves."""
    if scalar_leaves(variant):
        return w[K:].reshape(nf, K - 1), w[:K]
    W = w.reshape(nf, 2 * K - 1)
    return W[:, :K - 1], W[:, K - 1:]


def heap_probs(a):
    """(b, K-1) inner-node logits -> (b, K) leaf probabilities, a leaf's
    path walked from the leaf up to the root."""
    K = a.shape[-1] + 1
    left = jax.nn.sigmoid(a)
    cols = []
    for leaf in range(K):
        node, prob = K - 1 + leaf, jnp.ones(a.shape[:-1], a.dtype)
        while node > 0:
            parent = (node - 1) // 2
            went_left = node == 2 * parent + 1
            prob = prob * (left[..., parent] if went_left else 1.0 - left[..., parent])
            node = parent
        cols.append(prob)
    return jnp.stack(cols, axis=-1)


def make_tree(variant: str, nf: int, K: int, compute=jnp.float32):
    """tree(w, idx, val, mask) -> (b,) outputs of one block of rows."""
    hier = variant in ("gbhmlr", "gbhsdt")

    def tree(w, idx, val, mask):
        w, x = w.astype(compute), val.astype(compute)
        gates, experts = split(variant, w, nf, K)
        gates = gates * mask.astype(compute)[:, None]
        if scalar_leaves(variant):
            a = jnp.einsum("bj,bjp->bp", x, gates[idx])
        else:  # a feature's masked gates and its experts, looked up together
            rows = jnp.concatenate([gates, experts], axis=1)[idx]
            a = jnp.einsum("bj,bjp->bp", x, rows[..., :K - 1])
        if hier:
            pi = heap_probs(a)
        else:
            pi = jax.nn.softmax(jnp.concatenate([a, jnp.zeros_like(a[:, :1])], axis=1), axis=-1)
        if scalar_leaves(variant):
            return jnp.sum(pi * experts[None, :], axis=-1)
        e = jnp.einsum("bj,bjp->bp", x, rows[..., K - 1:])
        return jnp.sum(pi * e, axis=-1)

    return tree


def row_loss(s, y):
    """log(1 + exp(s)) - y s, the stable form."""
    return jnp.log1p(jnp.exp(-jnp.abs(s))) + jnp.maximum(s, 0.0) - s * y


def blocks(n: int, block: int):
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]


def make_pass(variant: str, nf: int, K: int, block: int, compute=jnp.float32):
    """loss_and_grad(w, idx, val, z, mask, y, wt) over all rows."""
    tree = make_tree(variant, nf, K, compute)

    def block_loss(w, idx, val, z, mask, y, wt):
        s = z + tree(w, idx, val, mask).astype(jnp.float32)
        return jnp.sum(wt * row_loss(s, y))

    vg = jax.jit(jax.value_and_grad(block_loss))

    def loss_and_grad(w, idx, val, z, mask, y, wt):
        with jax.default_matmul_precision("highest"):
            parts = [vg(w, idx[lo:hi], val[lo:hi], z[lo:hi], mask, y[lo:hi], wt[lo:hi])
                     for lo, hi in blocks(idx.shape[0], block)]
        loss = jnp.sum(jnp.stack([l for l, _ in parts]).astype(jnp.float32))
        grad = jnp.sum(jnp.stack([g.astype(jnp.float32) for _, g in parts]), axis=0)
        return loss, grad

    return loss_and_grad


def tree_output(variant: str, nf: int, K: int, block: int, w, idx, val, mask,
                compute=jnp.float32):
    """The tree's output over all rows, as float32, a block at a time."""
    tree = jax.jit(make_tree(variant, nf, K, compute))
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([tree(w, idx[lo:hi], val[lo:hi], mask).astype(jnp.float32)
                                for lo, hi in blocks(idx.shape[0], block)])


def mean_loss(z, y, wt, g_weight: float) -> float:
    """The ensemble's loss at scores z, divided by the weight sum."""
    per = jnp.where(wt > 0, row_loss(z, y), 0.0)
    return float(jnp.sum(wt * per)) / g_weight


def l2_vector(variant: str, nf: int, K: int, need_bias: bool, l2: float):
    v = np.full((dim(variant, nf, K),), l2, np.float32)
    if need_bias:  # the bias feature's block (sdt: its gates) carries none
        lo = K if scalar_leaves(variant) else 0
        v[lo:2 * K - 1] = 0.0
    return v


def init_weights(variant: str, nf: int, K: int, need_bias: bool, seed: int,
                 tree: int, init: dict) -> np.ndarray:
    """A tree's starting point: ytk-learn re-draws it for every tree from
    `seed + tree` (GBMLRDataFlow.initW), the bias feature's block zeroed;
    the sdt leaves uniform in `leaf_range`."""
    rng = np.random.RandomState(seed + tree)
    n = dim(variant, nf, K)
    if init["mode"] == "uniform":
        w = rng.uniform(init["range_start"], init["range_end"], n).astype(np.float32)
    else:
        w = (rng.randn(n) * init["std"] + init["mean"]).astype(np.float32)
    if scalar_leaves(variant):
        lo, hi = init["leaf_range"]
        w[:K] = rng.uniform(lo, hi, K).astype(np.float32)
        if need_bias:
            w[K:2 * K - 1] = 0.0
    elif need_bias:
        w[:2 * K - 1] = 0.0
    return w


class Masks:
    """The Bernoulli masks of tree 0, 1, ... in order (randomNextSample):
    rows kept with `instance_rate` and their weight scaled by its inverse,
    features kept with `feature_rate`, the bias always."""

    def __init__(self, seed: int, n: int, nf: int, instance_rate: float,
                 feature_rate: float, need_bias: bool):
        self.rows, self.feats = np.random.RandomState(seed), np.random.RandomState(seed + 104729)
        self.n, self.nf, self.need_bias = n, nf, need_bias
        self.instance_rate, self.feature_rate = instance_rate, feature_rate

    def next(self):
        """(row factor (n,), feature mask (nf,)) of the next tree."""
        keep = (self.rows.rand(self.n) <= self.instance_rate).astype(np.float32)
        feat = (self.feats.rand(self.nf) <= self.feature_rate).astype(np.float32)
        if self.need_bias:
            feat[0] = 1.0
        return keep / np.float32(self.instance_rate), feat


def masked(variant: str, w, feat, nf: int, K: int) -> np.ndarray:
    """The flat vector as a dump writes it: the gates of a feature the
    tree's mask left out are zeros (the bias feature is never left out)."""
    w = np.array(w, np.float32)
    gates = w[K:].reshape(nf, K - 1) if scalar_leaves(variant) else w.reshape(nf, 2 * K - 1)[:, :K - 1]
    gates[np.asarray(feat) == 0] = 0.0  # a view: writes into w
    return w


def parse_tree(text: str, variant: str, names: dict, K: int, delim: str = ",") -> np.ndarray:
    """The flat vector of one dumped tree (`tree-%05d/model-%05d`): a `k:K`
    line, for the sdt variants a bare line of K leaves, then a line a
    feature, `name,v0,...,` in the flat layout's order."""
    lines = [ln.strip() for ln in text.splitlines() if ln.strip()]
    if lines[0] != f"k:{K}":
        raise ValueError(f"dumped tree says {lines[0]!r}, not k:{K}")
    nf = len(names)
    w = np.zeros((dim(variant, nf, K),), np.float32)
    body = lines[1:]
    if scalar_leaves(variant):
        w[:K] = [float(v) for v in body[0].split(delim)[:K]]
        body = body[1:]
    stride = K - 1 if scalar_leaves(variant) else 2 * K - 1
    base = K if scalar_leaves(variant) else 0
    for ln in body:
        parts = [s for s in ln.split(delim) if s != ""]
        f = names[parts[0]]
        w[base + f * stride: base + (f + 1) * stride] = [float(v) for v in parts[1:1 + stride]]
    return w


def block_norms(variant: str, vec, nf: int, K: int) -> np.ndarray:
    """Norms of the two blocks of the flat vector: gates, experts."""
    gates, experts = split(variant, jnp.asarray(vec, jnp.float32), nf, K)
    return np.array([float(jnp.linalg.norm(gates)), float(jnp.linalg.norm(experts))])


def gaps(variant: str, prog: dict, ref: dict, nf: int, K: int) -> dict:
    """prog/ref: {"loss": [...], "g0", "w0", "w"} at the same iteration."""
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    n = min(len(lp), len(lr))

    def by_block(a, b):
        return norm_gap(block_norms(variant, a, nf, K), block_norms(variant, b, nf, K))

    return {
        "loss_gap": float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n]))),
        "grad_gap": by_block(prog["g0"], ref["g0"]),
        "dw_gap": by_block(jnp.asarray(prog["w"]) - jnp.asarray(prog["w0"]),
                           jnp.asarray(ref["w"]) - jnp.asarray(ref["w0"])),
    }
