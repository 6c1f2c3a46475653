"""Plain reference for boosted trees under logistic loss.

Imports nothing of the program. It is handed the benchmark's own rows (made
from the seed) and the text model the program dumped, which is the answer to
be judged, and it re-derives from the raw rows everything the model claims:

  * every row is routed through every tree by the dumped real-valued
    thresholds (so binning, the bin matrix and the slot-to-value conversion
    are all in what is judged), one node at a time in id order;
  * the f32 scores are the base score plus the leaves the rows land in, and
    from them the logistic loss after every tree and the gradient pair
    g = p - y, h = p (1 - p) that the next tree is fitted to;
  * for the trees that are checked, every node's row count, hessian sum,
    leaf value -lr * G / (H + l2) and split gain, from plain f32 sums of the
    unrounded g and h (the program rounds them to the histogram precision
    its configuration states; that rounding is the gap that is read);
  * for the root of every checked tree, the best gain any split allowed by
    the configuration could have had: the reference makes its own
    representatives by the published quantile rule (the sorted column's
    values at `quantile_bins` even ranks), takes the midpoints of
    neighbours as the candidate thresholds of every feature, sums g and h
    of the rows below each from the raw rows, and keeps the largest gain
    among the candidates that leave `min_child_hessian_sum` on both sides.
    The gain of the split the program chose, as the reference recomputes
    it, has to be that gain, and its threshold one of those candidates: a
    histogram that left out features or bins, or binned more coarsely,
    chooses a worse split or one off the grid.
"""

from __future__ import annotations

import re
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

NODE_RE = re.compile(r"^\t*(\d+):(.*)$")
SPLIT_RE = re.compile(r"^\[f_(.+)<=([^\]]+)\] yes=(\d+),no=(\d+),missing=(\d+)(.*)$")


def parse_model(text: str) -> dict:
    """The dumped text: header, then a `booster[i]` block a tree with one
    line a node, `id:[f_name<=thr] yes=..,no=..,missing=..,gain=..,hess_sum=
    ..,sample_cnt=..` or `id:leaf=..,hess_sum=..,sample_cnt=..`."""
    lines = text.split("\n")
    head = dict(l.split("=", 1) for l in lines[:4])
    trees: List[Dict[str, np.ndarray]] = []
    cur = None
    for line in lines[4:]:
        if line.startswith("booster["):
            cur = []
            trees.append(cur)
            continue
        m = NODE_RE.match(line)
        if not m or cur is None:
            continue
        nid, rest = int(m.group(1)), m.group(2)
        node = {"id": nid}
        if rest.startswith("leaf="):
            kv = dict(p.split("=") for p in rest.split(","))
            node.update(leaf=float(kv["leaf"]), hess=float(kv["hess_sum"]),
                        cnt=int(kv["sample_cnt"]), feat=-1)
        else:
            s = SPLIT_RE.match(rest)
            kv = dict(p.split("=") for p in s.group(6).strip(",").split(","))
            node.update(feat_name=s.group(1), thr=float(s.group(2)),
                        left=int(s.group(3)), right=int(s.group(4)),
                        gain=float(kv["gain"]), hess=float(kv["hess_sum"]),
                        cnt=int(kv["sample_cnt"]))
        cur.append(node)
    if len(trees) != int(head["tree_num"]):
        raise ValueError(f"tree_num={head['tree_num']} but {len(trees)} blocks")
    return {"base": float(head["base_prediction"]), "obj": head["obj"],
            "trees": trees}


def tree_arrays(nodes: List[dict], names: List[str], M: int) -> dict:
    col = {n: i for i, n in enumerate(names)}
    a = {
        "feat": np.full((M,), -1, np.int32), "thr": np.zeros((M,), np.float32),
        "left": np.zeros((M,), np.int32), "right": np.zeros((M,), np.int32),
        "leaf": np.zeros((M,), np.float32), "hess": np.zeros((M,), np.float64),
        "cnt": np.zeros((M,), np.int64), "gain": np.zeros((M,), np.float64),
        "used": np.zeros((M,), bool),
    }
    for nd in nodes:
        i = nd["id"]
        a["used"][i] = True
        a["hess"][i], a["cnt"][i] = nd["hess"], nd["cnt"]
        if nd.get("feat") == -1:
            a["leaf"][i] = nd["leaf"]
        else:
            a["feat"][i] = col[nd["feat_name"]]
            a["thr"][i] = nd["thr"]
            a["left"][i], a["right"][i] = nd["left"], nd["right"]
            a["gain"][i] = nd["gain"]
            if not (nd["left"] > i and nd["right"] > i):
                raise ValueError("a child's id is not above its parent's")
    return a


@jax.jit
def route(X_t, feat, thr, left, right):
    """Leaf id of every row: nodes in id order, a child's id above its
    parent's, so one sweep routes every row to its leaf. Also which rows met
    a threshold they equal exactly: the model's `<=` sends such a row left,
    the published binning rule (nearest representative, ties to the upper
    one) puts it in the upper bin, so the text leaves its side open."""
    n = X_t.shape[1]

    def body(i, carry):
        pos, tie = carry
        f = feat[i]
        x = jax.lax.dynamic_index_in_dim(X_t, jnp.maximum(f, 0), 0, keepdims=False)
        here = (pos == i) & (f >= 0)
        nxt = jnp.where(x <= thr[i], left[i], right[i])
        return jnp.where(here, nxt, pos), tie | (here & (x == thr[i]))

    return jax.lax.fori_loop(0, feat.shape[0], body,
                             (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool)))


@jax.jit
def leaf_sums(pos, g, h, n_nodes_arr):
    """(M, 3) f32: sum g, sum h, count of the rows at each node id."""
    M = n_nodes_arr.shape[0]

    def body(i, acc):
        m = pos == i
        row = jnp.stack([
            jnp.sum(jnp.where(m, g, 0.0)), jnp.sum(jnp.where(m, h, 0.0)),
            jnp.sum(m.astype(jnp.float32)),
        ])
        return acc.at[i].set(row)

    return jax.lax.fori_loop(0, M, body, jnp.zeros((M, 3), jnp.float32))


@jax.jit
def grad_hess(scores, y):
    p = 1.0 / (1.0 + jnp.exp(-scores))
    return p - y, p * (1.0 - p)


@jax.jit
def mean_logloss(scores, y):
    # log(1 + exp(-|s|)) + max(s, 0) - s*y, the stable form
    per = jnp.log1p(jnp.exp(-jnp.abs(scores))) + jnp.maximum(scores, 0.0) - scores * y
    return jnp.mean(per)


def candidate_thresholds(X_t, quantile_bins: int):
    """(F, quantile_bins - 1) thresholds a split may have. The published
    rule (sample_by_quantile, equal weights): a feature's representatives
    are the values of its sorted column at the even ranks
    ceil(k n / quantile_bins) - 1, k = 1..quantile_bins; a row belongs to
    the nearest representative, so the boundary between two neighbours, and
    the value a split on it is dumped with, is their midpoint."""
    n = X_t.shape[1]
    k = np.arange(1, quantile_bins + 1, dtype=np.float64)
    pos = np.clip(np.ceil(k / quantile_bins * n).astype(np.int64) - 1, 0, n - 1)
    reps = jnp.sort(X_t, axis=1)[:, jnp.asarray(pos, jnp.int32)]
    return 0.5 * (reps[:, :-1] + reps[:, 1:])


@jax.jit
def sums_below(X_t, cand, g, h):
    """(F, J, 2) f32: for every feature and candidate threshold the sums of
    g and of h over the rows whose value lies below it, a block of rows at
    a time (the last block starts early and counts only its fresh rows)."""
    n = X_t.shape[1]
    m = min(n, 1 << 18)

    def per_feature(args):
        x, t = args

        def body(c, acc):
            lo = c * m
            start = jnp.minimum(lo, n - m)
            xs = jax.lax.dynamic_slice(x, (start,), (m,))
            gs = jax.lax.dynamic_slice(g, (start,), (m,))
            hs = jax.lax.dynamic_slice(h, (start,), (m,))
            fresh = start + jnp.arange(m) >= lo
            below = (xs[None, :] < t[:, None]) & fresh[None, :]
            return acc + jnp.stack([
                jnp.sum(jnp.where(below, gs[None, :], 0.0), axis=1),
                jnp.sum(jnp.where(below, hs[None, :], 0.0), axis=1)], axis=1)

        return jax.lax.fori_loop(0, -(-n // m), body,
                                 jnp.zeros((t.shape[0], 2), jnp.float32))

    return jax.lax.map(per_feature, (X_t, cand))


def root_check(a: dict, cand, sums: np.ndarray, G: float, H: float,
               l2: float, min_h: float) -> dict:
    """The best gain over every candidate of every feature, and how far the
    root's dumped threshold lies from the nearest candidate of its feature,
    in units of the candidates' spacing there."""
    GL, HL = sums[..., 0], sums[..., 1]
    GR, HR = G - GL, H - HL

    def score(g, hh):
        return g * g / np.maximum(hh + l2, 1e-300)

    gain = score(GL, HL) + score(GR, HR) - score(G, H)
    gain = np.where((HL >= min_h) & (HR >= min_h), gain, -np.inf)
    f_best, j_best = np.unravel_index(int(np.argmax(gain)), gain.shape)
    out = {"root_best": float(gain[f_best, j_best]), "root_best_feat": int(f_best)}
    if a["feat"][0] >= 0:
        t = np.asarray(cand[int(a["feat"][0])], np.float64)
        j = int(np.argmin(np.abs(t - float(a["thr"][0]))))
        spacing = 0.5 * (t[min(j + 1, len(t) - 1)] - t[max(j - 1, 0)])
        out["root_thr_off"] = float(abs(t[j] - float(a["thr"][0])) / max(spacing, 1e-300))
    return out


def follow(model: dict, names: List[str], X, y, X_test, y_test,
           check_trees: List[int], lr: float, l2: float, min_h: float,
           quantile_bins: int, M: int = 512) -> dict:
    """Re-derive the model's claims. Returns per-tree train/test loss, the
    final scores, and for each checked tree the reference's and the
    program's node statistics."""
    X_t = jnp.transpose(jnp.asarray(X))
    Xt_t = jnp.transpose(jnp.asarray(X_test)) if X_test is not None else None
    y = jnp.asarray(y, jnp.float32)
    scores = jnp.full((X_t.shape[1],), model["base"], jnp.float32)
    scores_t = None
    if Xt_t is not None:
        y_test = jnp.asarray(y_test, jnp.float32)
        scores_t = jnp.full((Xt_t.shape[1],), model["base"], jnp.float32)
    loss, loss_t, checked = [], [], {}
    marker = jnp.zeros((M,), jnp.int32)
    ever = jnp.zeros((X_t.shape[1],), bool)
    ever_t = None if Xt_t is None else jnp.zeros((Xt_t.shape[1],), bool)
    cand = candidate_thresholds(X_t, quantile_bins)
    for t, nodes in enumerate(model["trees"]):
        a = tree_arrays(nodes, names, M)
        dev = [jnp.asarray(a[k]) for k in ("feat", "thr", "left", "right")]
        pos, tie = route(X_t, *dev)
        ever = ever | tie
        if t in check_trees:
            g, h = grad_hess(scores, y)
            sums = np.asarray(leaf_sums(pos, g, h, marker), np.float64)
            checked[t] = node_stats(a, sums, lr, l2, min_h)
            checked[t]["ties"] = int(jnp.sum(tie))
            below = np.asarray(sums_below(X_t, cand, g, h), np.float64)
            checked[t].update(root_check(
                a, cand, below, float(checked[t]["G_ref"][0]),
                float(checked[t]["hess_ref"][0]), l2, min_h))
        leaf = jnp.asarray(a["leaf"])
        scores = scores + leaf[pos]
        loss.append(float(mean_logloss(scores, y)))
        if Xt_t is not None:
            pos_t, tie_t = route(Xt_t, *dev)
            ever_t = ever_t | tie_t
            scores_t = scores_t + leaf[pos_t]
            loss_t.append(float(mean_logloss(scores_t, y_test)))
    return {"loss": loss, "loss_test": loss_t, "scores": scores,
            "scores_test": scores_t, "checked": checked, "ties": ever,
            "ties_test": ever_t}


def node_stats(a: dict, leaf_sum: np.ndarray, lr: float, l2: float,
               min_h: float = 0.0) -> dict:
    """Totals of every node from its leaves' sums (children before parents:
    ids descend), then the value and gain formulas of the configuration."""
    M = a["feat"].shape[0]
    tot = np.where(a["used"][:, None] & (a["feat"] < 0)[:, None], leaf_sum, 0.0)
    for i in range(M - 1, -1, -1):
        if a["used"][i] and a["feat"][i] >= 0:
            tot[i] = tot[a["left"][i]] + tot[a["right"][i]]
    G, H, C = tot[:, 0], tot[:, 1], tot[:, 2]

    # The configuration's min_child_hessian_sum decides whether a split is
    # taken, by the program's own sums; a node that is in the model passed
    # it, so the guard is not applied a second time to the reference's sums
    # (a sum a rounding away from the threshold would read as a gap of 1).
    def score(g, hh):
        return g * g / np.maximum(hh + l2, 1e-300)

    leaf_ref = -lr * G / np.maximum(H + l2, 1e-300)
    gain_ref = np.zeros((M,))
    inner = a["used"] & (a["feat"] >= 0)
    li, ri = a["left"][inner], a["right"][inner]
    gain_ref[inner] = (score(G[li], H[li]) + score(G[ri], H[ri])
                       - score(G[inner], H[inner]))
    return {"is_leaf": a["used"] & (a["feat"] < 0), "inner": inner,
            "cnt_ref": C, "G_ref": G, "hess_ref": H, "leaf_ref": leaf_ref,
            "gain_ref": gain_ref, "cnt": a["cnt"], "hess": a["hess"],
            "leaf": a["leaf"].astype(np.float64), "gain": a["gain"]}


def gaps(out: dict, loss_prog, loss_test_prog, scores_prog, scores_test_prog) -> dict:
    """The numbers that are compared, each the worst over what was checked.
    A relative gap is measured against the reference's value of that node or
    of the tree's median node, whichever is larger."""
    def rel_all(prog, ref, mask):
        ref_m = np.abs(ref[mask])
        floor = np.maximum(ref_m, np.median(ref_m))
        return np.abs(prog[mask] - ref[mask]) / np.maximum(floor, 1e-300)

    res = {"cnt_gap": 0.0, "root_gain_gap": 0.0, "root_thr_off": 0.0}
    trees = sorted(out["checked"])
    half = (len(trees) + 1) // 2
    for part, which in (("first", trees[:half]), ("last", trees[half:])):
        for name in ("leaf_gap", "hess_gap", "gain_gap", "leaf_med", "hess_med"):
            res[f"{name}.{part}"] = 0.0
        for t in which:
            st = out["checked"][t]
            used = st["is_leaf"] | st["inner"]
            # the root's split against the best any candidate could have had
            res["root_gain_gap"] = max(res["root_gain_gap"], float(abs(
                st["root_best"] - st["gain_ref"][0]) / max(st["root_best"], 1e-300)))
            res["root_thr_off"] = max(res["root_thr_off"], st.get("root_thr_off", 1.0))
            # a row on a threshold may sit on either side: so many rows of
            # difference are no gap
            off = np.abs(st["cnt"][used] - st["cnt_ref"][used]) - st["ties"]
            res["cnt_gap"] = max(res["cnt_gap"], float(np.max(np.maximum(off, 0.0))))
            for name, key, mask in (("leaf", "leaf", st["is_leaf"]),
                                    ("hess", "hess", used),
                                    ("gain", "gain", st["inner"])):
                if not mask.any():
                    continue
                r = rel_all(st[key], st[key + "_ref"], mask)
                res[f"{name}_gap.{part}"] = max(res[f"{name}_gap.{part}"], float(r.max()))
                if name != "gain":  # the median node's gap: steady from seed to seed
                    res[f"{name}_med.{part}"] = max(
                        res[f"{name}_med.{part}"], float(np.median(r)))
    lp, lr_ = np.asarray(loss_prog, np.float64), np.asarray(out["loss"], np.float64)
    res["loss_gap"] = float(np.max(np.abs(lp - lr_) / lr_))
    if out["loss_test"]:
        tp = np.asarray(loss_test_prog, np.float64)
        tr = np.asarray(out["loss_test"], np.float64)
        res["test_loss_gap"] = float(np.max(np.abs(tp - tr) / tr))
    # scores: every row that met no threshold it equals
    n = out["scores"].shape[0]
    d = jnp.abs(jnp.asarray(scores_prog)[:n] - out["scores"])
    res["score_gap"] = float(jnp.max(jnp.where(out["ties"], 0.0, d)))
    res["tie_rows"] = float(jnp.sum(out["ties"]))
    res["tie_score_gap"] = float(jnp.max(jnp.where(out["ties"], d, 0.0)))
    if out["scores_test"] is not None:
        nt = out["scores_test"].shape[0]
        d = jnp.abs(jnp.asarray(scores_test_prog)[:nt] - out["scores_test"])
        res["score_gap"] = max(res["score_gap"], float(
            jnp.max(jnp.where(out["ties_test"], 0.0, d))))
        res["tie_rows"] += float(jnp.sum(out["ties_test"]))
    return res
