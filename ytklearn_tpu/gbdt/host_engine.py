"""GBDT host engine — the original per-level / per-split host loop.

The reference implementation for equivalence tests, and the only path for
precise LAD leaf refinement (lad_refine_appr=false: a host-side weighted
median, reference TreeRefiner.java:72-123) and the feature-parallel maker
(gbdt/feature_parallel.py). It cannot run multi-process, GOSS or EFB.
Every function takes the GBDTTrainer it works for and calls on it what
both engines share (base score, resume, tree conversion, dumps).
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import TYPE_CHECKING, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..eval import EvalSet
from ..obs import inc as obs_inc
from .binning import bin_matrix, build_bins_global
from .data import GBDTData, GBDTIngest
from .engine import split_kernel
from .tree import GBDTModel, Tree, _traverse_kernel, _wavg_loss

if TYPE_CHECKING:
    from .trainer import GBDTResult

log = logging.getLogger("ytklearn_tpu.gbdt")


@partial(jax.jit, static_argnames=("n_nodes", "F", "B"))
def hist_kernel(bins, pos, g, h, n_nodes: int, F: int, B: int):
    """(n_nodes, F, B, 3) histogram of (g, h, count) by level-local node.

    pos < 0 = inactive sample -> dump segment. Scatter-add formulation —
    fine on CPU, slow on TPU (the device engine uses gbdt/hist.py)."""
    n = bins.shape[0]
    active = pos >= 0
    base = jnp.where(active, pos, n_nodes) * (F * B)
    ids = base[:, None] + jnp.arange(F)[None, :] * B + bins  # (n, F)
    vals = jnp.stack(
        [g, h, jnp.where(active, 1.0, 0.0)], axis=1
    )  # (n, 3)
    flat = jnp.zeros(((n_nodes + 1) * F * B, 3), jnp.float32)
    flat = flat.at[ids.reshape(-1)].add(
        jnp.repeat(vals, F, axis=0).reshape(n, F, 3).reshape(-1, 3)
    )
    return flat[: n_nodes * F * B].reshape(n_nodes, F, B, 3)


@jax.jit
def pos_update_kernel(bins, pos, node_feat, node_slot, node_child_base):
    """Route samples to next-level-local child indices.

    node_child_base[k] = left-child index among next level's nodes, or -1 if
    node k became a leaf (reference: SamplePositionData.resetPosition:115)."""
    safe = jnp.maximum(pos, 0)
    f = node_feat[safe]
    slot = node_slot[safe]
    base = node_child_base[safe]
    b = jnp.take_along_axis(bins, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
    go_right = b > slot
    new = jnp.where(base >= 0, base + go_right.astype(jnp.int32), -1)
    return jnp.where(pos >= 0, new, -1)


@partial(jax.jit, static_argnames=("F", "B"))
def node_hist_kernel(bins, in_node, g, h, F: int, B: int):
    """(F, B, 3) histogram for one node's samples (host loss-wise growth)."""
    ids = jnp.where(in_node[:, None], jnp.arange(F)[None, :] * B + bins, F * B)
    vals = jnp.stack([g, h, jnp.where(in_node, 1.0, 0.0)], axis=1)
    n = bins.shape[0]
    flat = jnp.zeros((F * B + 1, 3), jnp.float32)
    flat = flat.at[ids.reshape(-1)].add(
        jnp.repeat(vals, F, axis=0).reshape(n, F, 3).reshape(-1, 3)
    )
    return flat[: F * B].reshape(F, B, 3)


def _decide_split(trainer, chg, cl, cr, hl, hr) -> bool:
    p = trainer.params
    return (
        np.isfinite(chg)
        and chg > p.min_split_loss
        and cl + cr >= p.min_split_samples
        and (hl + hr) >= p.min_child_hessian_sum * 2.0
    )

def _finish_split(trainer, tree, bins_meta, nid, fid, slot_l, slot_r, stats):
    """Record a split on the host tree (slot-space; converted at dump)."""
    gl, hl, cl, gr, hr, cr = stats
    tree.feat[nid] = fid
    tree.feat_name[nid] = bins_meta[fid] if bins_meta else str(fid)
    tree.slot[nid] = slot_l
    tree.split[nid] = float(slot_l)  # slot until convert
    left, right = tree.add_children(nid)
    # f32 multiply, bit-identical to the device engine's leaf values
    lr = np.float32(trainer.params.learning_rate)
    tree.leaf_value[left] = float(np.float32(trainer.node_value_fn(gl, hl)) * lr)
    tree.leaf_value[right] = float(np.float32(trainer.node_value_fn(gr, hr)) * lr)
    tree.hess_sum[left], tree.sample_cnt[left] = float(hl), int(cl)
    tree.hess_sum[right], tree.sample_cnt[right] = float(hr), int(cr)
    return left, right

def build_tree_level_wise(
    trainer, bins_dev, g, h, pos0, F: int, B: int, feat_mask, names
) -> Tree:
    """Level-synchronous growth: one histogram scan + one split search +
    one position update per level (reference level policy,
    DataParallelTreeMaker.make with TreeGrowPolicy.LEVEL)."""
    p = trainer.params
    tree = Tree()
    pos = pos0  # level-local node index per sample (-1 inactive)
    level_nids = [0]  # tree nid per level-local index
    # root stats
    root_hist = hist_kernel(bins_dev, pos, g, h, 1, F, B)
    ghc = np.asarray(jnp.sum(root_hist, axis=(1, 2)))[0] / F  # sums counted F times
    tree.hess_sum[0], tree.sample_cnt[0] = float(ghc[1]), int(round(ghc[2]))
    tree.leaf_value[0] = float(
        np.float32(trainer.node_value_fn(ghc[0], ghc[1]))
        * np.float32(p.learning_rate)
    )
    cfg = trainer._cfg()
    max_leaves = p.max_leaf_cnt if p.max_leaf_cnt > 0 else 1 << 30
    max_depth = p.max_depth if p.max_depth > 0 else 1 << 30

    for depth in range(max_depth):
        n_nodes = len(level_nids)
        if n_nodes == 0:
            break
        n_pad = 1 << (n_nodes - 1).bit_length()  # pad node count: few shapes
        hist = hist_kernel(bins_dev, pos, g, h, n_pad, F, B)
        out = split_kernel(hist, feat_mask, cfg)
        (chg, flat_idx, slot_l, GL, HL, CL, GR, HR, CR) = (
            np.asarray(o) for o in out
        )

        node_feat = np.full((n_pad,), -1, np.int32)
        node_slot = np.full((n_pad,), 0, np.int32)
        child_base = np.full((n_pad,), -1, np.int32)
        next_nids: List[int] = []
        leaves_after = tree.leaf_cnt()
        for k in range(n_nodes):
            nid = level_nids[k]
            can = (
                depth < max_depth
                and leaves_after + 1 < max_leaves + 1
                and _decide_split(trainer, chg[k], CL[k], CR[k], HL[k], HR[k])
            )
            if not can:
                continue
            fid = int(flat_idx[k]) // B
            slot_right = int(flat_idx[k]) % B
            left, right = _finish_split(
                trainer,
                tree,
                names,
                nid,
                fid,
                int(slot_l[k]),
                slot_right,
                (GL[k], HL[k], CL[k], GR[k], HR[k], CR[k]),
            )
            tree.gain[nid] = float(chg[k])
            # store the interval's right end for split-value conversion
            tree.slot[nid] = int(slot_l[k])
            tree.split[nid] = float(slot_right)
            node_feat[k] = fid
            node_slot[k] = int(slot_l[k])
            child_base[k] = len(next_nids)
            next_nids.extend([left, right])
            leaves_after = tree.leaf_cnt()
        if not next_nids:
            break
        pos = pos_update_kernel(
            bins_dev,
            pos,
            jnp.asarray(node_feat),
            jnp.asarray(node_slot),
            jnp.asarray(child_base),
        )
        level_nids = next_nids

    return tree

def build_tree_loss_wise(
    trainer, bins_dev, g, h, pos_active, F: int, B: int, feat_mask, names
) -> Tree:
    """Best-first growth with per-node histograms + sibling subtraction
    (reference TreeGrowPolicy.LOSS + HistogramPool)."""
    p = trainer.params
    tree = Tree()
    cfg = trainer._cfg()
    # tree_pos: tree nid per sample (-1 = excluded by instance sampling)
    tree_pos = jnp.where(pos_active >= 0, 0, -1)

    root_hist = node_hist_kernel(bins_dev, tree_pos >= 0, g, h, F, B)
    hists: Dict[int, jnp.ndarray] = {0: root_hist}
    s = np.asarray(jnp.sum(root_hist[..., :], axis=(0, 1)))  # counted once per f
    Gt, Ht, Ct = s[0] / F, s[1] / F, s[2] / F
    tree.hess_sum[0], tree.sample_cnt[0] = float(Ht), int(round(Ct))
    tree.leaf_value[0] = float(
        np.float32(trainer.node_value_fn(Gt, Ht)) * np.float32(p.learning_rate)
    )

    def best_of(nid):
        out = split_kernel(hists[nid][None], feat_mask, cfg)
        return tuple(np.asarray(o)[0] for o in out)

    frontier = {0: best_of(0)}
    max_leaves = p.max_leaf_cnt if p.max_leaf_cnt > 0 else 1 << 30
    depth_of = {0: 0}
    max_depth = p.max_depth if p.max_depth > 0 else 1 << 30

    while tree.leaf_cnt() < max_leaves:
        # pick the best expandable frontier node
        cand = [
            (v[0], nid)
            for nid, v in frontier.items()
            if depth_of[nid] < max_depth
            and _decide_split(trainer, v[0], v[5], v[8], v[4], v[7])
        ]
        if not cand:
            break
        chg, nid = max(cand, key=lambda t: (t[0], -t[1]))
        (c, flat_idx, slot_l, GL, HL, CL, GR, HR, CR) = frontier.pop(nid)
        fid = int(flat_idx) // B
        slot_right = int(flat_idx) % B
        left, right = _finish_split(
            trainer, tree, names, nid, fid, int(slot_l), slot_right,
            (GL, HL, CL, GR, HR, CR),
        )
        tree.gain[nid] = float(c)
        tree.slot[nid] = int(slot_l)
        tree.split[nid] = float(slot_right)
        depth_of[left] = depth_of[right] = depth_of[nid] + 1

        # route samples of nid to children
        b = jnp.take_along_axis(bins_dev, jnp.full((bins_dev.shape[0], 1), fid), 1)[:, 0]
        in_nid = tree_pos == nid
        tree_pos = jnp.where(
            in_nid, jnp.where(b > int(slot_l), right, left), tree_pos
        )

        # smaller child by scan; sibling by subtraction (HistogramPool)
        small, big = (left, right) if CL <= CR else (right, left)
        small_hist = node_hist_kernel(bins_dev, tree_pos == small, g, h, F, B)
        parent_hist = hists.pop(nid)
        hists[small] = small_hist
        hists[big] = parent_hist - small_hist
        frontier[small] = best_of(small)
        frontier[big] = best_of(big)

    return tree

def _tree_scores_dev(trainer, tree: Tree, bins_dev) -> jnp.ndarray:
    """Slot-space tree traversal on device (bin <= slot goes left)."""
    feat = jnp.asarray(np.asarray(tree.feat, np.int32))
    slot = jnp.asarray(np.asarray(tree.slot, np.int32))
    left = jnp.asarray(np.asarray(tree.left, np.int32))
    right = jnp.asarray(np.asarray(tree.right, np.int32))
    leaf = jnp.asarray(np.asarray(tree.leaf_value, np.float32))
    depth = max(tree.max_depth(), 1)
    return _traverse_kernel(bins_dev, feat, slot, left, right, leaf, depth)

def train_host(
    trainer,
    train: Optional[GBDTData] = None,
    test: Optional[GBDTData] = None,
) -> GBDTResult:
    p = trainer.params
    t0 = time.time()
    if train is None:
        train, test = GBDTIngest(p, trainer.fs).load()
    if trainer.mesh is not None:
        train = train.pad_rows(trainer.mesh.devices.size)
        test = test.pad_rows(trainer.mesh.devices.size) if test else None
    n, F = train.X.shape
    K = trainer.K

    trainer._missing_fill = train.missing_fill
    log.info("building bins (%d features)...", F)
    bins = build_bins_global(train.X, train.weight, p, train.feature_names)
    trainer._bins_sidecar = (list(train.feature_names or []), bins)
    trainer._quality_features = trainer._build_quality_features(train)
    B = bins.max_bins
    bins_np = bin_matrix(train.X, bins)
    bins_train = trainer._put(bins_np)

    feature_parallel = p.tree_maker == "feature" and trainer.mesh is not None
    if feature_parallel:
        # columns sharded over the mesh (FeatureParallelTreeMakerByLevel);
        # the maker is level-wise only, as in the reference
        from .feature_parallel import shard_features

        bins_t_fp, F_pad_fp = shard_features(trainer.mesh, bins_np)
        if p.tree_grow_policy != "level":
            log.info(
                "tree_maker=feature grows level-wise (reference maker is "
                "ByLevel); ignoring tree_grow_policy=%r", p.tree_grow_policy
            )
    del bins_np
    y = trainer._put(train.y)
    weight = trainer._put(train.weight)
    log.info(
        "load+preprocess %.1fs: %d rows, %d features, %d max bins",
        time.time() - t0,
        train.n_real,
        F,
        B,
    )

    base_np = trainer._base_score(train, K)
    model = GBDTModel(
        base_prediction=float(np.mean(base_np)),
        num_tree_in_group=K,
        obj_name=trainer.loss.name,
    )

    # continue_train: reload + replay scores
    model, start_round = trainer._load_resume_model(
        model, K, feature_names=train.feature_names
    )

    if K > 1:
        scores = jnp.full((n, K), base_np, jnp.float32)
    else:
        scores = jnp.full((n,), float(base_np), jnp.float32)
    for i, t in enumerate(model.trees):
        add = trainer._tree_scores_from_raw(t, bins, bins_train)
        if K > 1:
            scores = scores.at[:, i % K].add(add)
        else:
            scores = scores + add

    eval_set = EvalSet(p.eval_metric, K=max(K, 2)) if p.eval_metric else None
    rng = np.random.RandomState(20170425)
    feat_names = train.feature_names
    round_log: List[Dict] = []

    test_state = None
    if test is not None:
        bins_test = trainer._put(bin_matrix(test.X, bins))
        y_t = trainer._put(test.y)
        w_t = trainer._put(test.weight)
        if K > 1:
            scores_t = jnp.full((test.n, K), base_np, jnp.float32)
        else:
            scores_t = jnp.full((test.n,), float(base_np), jnp.float32)
        for i, t in enumerate(model.trees):
            add = trainer._tree_scores_from_raw(t, bins, bins_test)
            if K > 1:
                scores_t = scores_t.at[:, i % K].add(add)
            else:
                scores_t = scores_t + add
        test_state = (bins_test, y_t, w_t, scores_t)

    if p.just_evaluate:
        return trainer._finalize(
            model, scores, y, weight, test_state, eval_set, round_log, bins
        )

    for rnd in range(start_round, p.round_num):
        if trainer._guard is not None and trainer._guard.triggered:
            # host engine appends converted trees as it goes: the dump
            # is the checkpoint, resume re-enters at this round
            trainer._dump_model(model)
            trainer._guard.preempt(
                p.model.data_path, family="gbdt_host", rounds=rnd,
                trees=len(model.trees),
            )
        # fast-path grads from predictions (reference:
        # ILossFunction.getDerivativeFast, GBDTOptimizer:513)
        preds = trainer.loss.predict(scores)
        gs, hs = trainer.loss.grad_hess(preds, y)
        # instance sampling + weight fold-in
        inst = (rng.rand(n) <= p.instance_sample_rate).astype(np.float32)
        inst[train.n_real :] = 0.0
        pos0 = jnp.asarray(np.where(inst > 0, 0, -1).astype(np.int32))
        fmask = (rng.rand(F) <= p.feature_sample_rate).astype(bool)
        if not fmask.any():
            fmask[rng.randint(F)] = True
        fmask_dev = jnp.asarray(fmask)

        obs_inc("gbdt.rounds")
        for grp in range(K):
            g = (gs[:, grp] if K > 1 else gs) * weight
            h = (hs[:, grp] if K > 1 else hs) * weight
            if feature_parallel:
                from .feature_parallel import build_tree_level_feature_parallel

                tree = build_tree_level_feature_parallel(
                    trainer, trainer.mesh, bins_t_fp, F_pad_fp, g, h, pos0,
                    F, B, fmask_dev, feat_names,
                )
            elif p.tree_grow_policy == "loss":
                tree = build_tree_loss_wise(
                    trainer, bins_train, g, h, pos0, F, B, fmask_dev, feat_names
                )
            else:
                tree = build_tree_level_wise(
                    trainer, bins_train, g, h, pos0, F, B, fmask_dev, feat_names
                )
            if trainer.loss.name == "l1" and K == 1:
                _refine_lad(trainer, tree, bins_train, y, scores, weight)
            add = _tree_scores_dev(trainer, tree, bins_train)
            if K > 1:
                scores = scores.at[:, grp].add(add)
            else:
                scores = scores + add
            if test_state is not None:
                add_t = _tree_scores_dev(trainer, tree, test_state[0])
                bins_test, y_t, w_t, scores_t = test_state
                if K > 1:
                    scores_t = scores_t.at[:, grp].add(add_t)
                else:
                    scores_t = scores_t + add_t
                test_state = (bins_test, y_t, w_t, scores_t)
            trainer._convert_tree(tree, bins)
            model.trees.append(tree)

        rec = {"round": rnd, "elapsed": time.time() - t0}
        rec["train_loss"] = float(_wavg_loss(trainer.loss, scores, y, weight))
        if test_state is not None:
            rec["test_loss"] = float(
                _wavg_loss(trainer.loss, test_state[3], test_state[1], test_state[2])
            )
        if eval_set is not None and (p.watch_train or p.watch_test or rnd == p.round_num - 1):
            if p.watch_train:
                rec["train_metrics"] = eval_set.evaluate(
                    trainer.loss.predict(scores), y, weight
                )
            if p.watch_test and test_state is not None:
                rec["test_metrics"] = eval_set.evaluate(
                    trainer.loss.predict(test_state[3]), test_state[1], test_state[2]
                )
        round_log.append(rec)
        log.info(
            "[round=%d] %.1fs train loss=%.6f%s",
            rnd,
            rec["elapsed"],
            rec["train_loss"],
            f" test loss={rec['test_loss']:.6f}" if "test_loss" in rec else "",
        )

        if p.model.dump_freq > 0 and (rnd + 1) % p.model.dump_freq == 0:
            trainer._dump_model(model)

    if test_state is not None:
        trainer._stash_quality_scores(test_state[3], test_state[2])
    else:
        trainer._stash_quality_scores(scores, weight)
    trainer._dump_model(model)
    return trainer._finalize(
        model, scores, y, weight, test_state, eval_set, round_log, bins
    )

def _refine_lad(trainer, tree: Tree, bins_dev, y, scores, weight) -> None:
    """LAD leaf refinement: leaf value = lr * weighted median of
    (y - current score) over the leaf's samples (reference:
    optimizer/gbdt/TreeRefiner.java:72-123, precise mode)."""
    pos = np.asarray(_tree_leaf_assignment(trainer, tree, bins_dev))
    resid = np.asarray(y) - np.asarray(scores)
    w = np.asarray(weight)
    lr = trainer.params.learning_rate
    for nid in range(tree.n_nodes()):
        if not tree.is_leaf(nid):
            continue
        m = (pos == nid) & (w > 0)
        if not m.any():
            continue
        r, ww = resid[m], w[m]
        order = np.argsort(r, kind="stable")
        cw = np.cumsum(ww[order])
        cut = 0.5 * cw[-1]
        tree.leaf_value[nid] = float(r[order][np.searchsorted(cw, cut)]) * lr

def _tree_leaf_assignment(trainer, tree: Tree, bins_dev):
    feat = jnp.asarray(np.asarray(tree.feat, np.int32))
    slot = jnp.asarray(np.asarray(tree.slot, np.int32))
    left = jnp.asarray(np.asarray(tree.left, np.int32))
    right = jnp.asarray(np.asarray(tree.right, np.int32))
    depth = max(tree.max_depth(), 1)
    return _assign_kernel(bins_dev, feat, slot, left, right, depth)


@partial(jax.jit, static_argnames=("depth",))
def _assign_kernel(bins, feat, slot, left, right, depth: int):
    n = bins.shape[0]
    node = jnp.zeros((n,), jnp.int32)

    def step(_, node):
        f = feat[node]
        is_leaf = f < 0
        b = jnp.take_along_axis(bins, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        nxt = jnp.where(b <= slot[node], left[node], right[node])
        return jnp.where(is_leaf, node, nxt)

    return jax.lax.fori_loop(0, depth, step, node)
