"""GBST boosting driver — the GBMLROperation equivalent.

Rebuild of reference operation/GBMLROperation.java:39-124: per tree, run a
full L-BFGS fit of the soft-tree mixture against the residual objective
(loss evaluated at z + tree output), then fold the finished tree into z with
the learning rate (GBMLRDataFlow.accumulate:540), re-randomize the
instance/feature Bernoulli masks, re-init weights, and continue. Supports
gradient_boosting and random_forest types, continue_train via the
tree-info + tree-%05d model files.

A tree's turn, as the spans have it (docs/observability.md): `gbst.tree`
(a step) > `gbst.masks` (the host's mask draws, the weights' round trip,
the re-init), the fit's `lbfgs.*`, `gbst.fold` (the tree folded into the
train and test scores and the ensemble losses, device-settled), `gbst.dump`.
The fold scans the rows in the fit's chunks (`optimize/blocked.py`): whole,
its gathered `(rows, width, stride)` intermediate is rows x 16 KiB.

Which id each slot holds is read off the rows once at set-up, train and
test rows each by themselves (`io/reader.py::constant_slots`): where every
slot of a set of rows holds one id in every row, that set's model instance
evaluates a tree as a product with the table's rows and looks nothing up a
slot (`models/gbst.py`); any other rows keep the lookup.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config.params import CommonParams
from .eval import EvalSet
from .io.fs import FileSystem, LocalFileSystem
from .io.reader import DataIngest, IngestResult, constant_slots
from .losses import create_loss
from .models.gbst import GBSTModel
from .obs import (
    gauge as obs_gauge,
    health,
    inc as obs_inc,
    root_span as obs_root_span,
    span as obs_span,
    step_span as obs_step_span,
)
from .obs.scopes import Program
from .optimize import LBFGSConfig, minimize_lbfgs
from .optimize.blocked import make_rows
from .resilience import trainer_guard

log = logging.getLogger("ytklearn_tpu.boost")


@dataclass
class BoostResult:
    n_trees: int
    train_loss: float  # avg loss of the accumulated ensemble
    test_loss: Optional[float]
    train_metrics: Dict[str, float] = field(default_factory=dict)
    test_metrics: Dict[str, float] = field(default_factory=dict)
    per_tree_loss: List[float] = field(default_factory=list)


class GBSTTrainer:
    """Boosted soft-tree trainer for gbmlr/gbsdt/gbhmlr/gbhsdt."""

    def __init__(
        self,
        params: CommonParams,
        variant: str,
        mesh=None,
        fs: Optional[FileSystem] = None,
    ):
        self.params = params
        self.variant = variant
        self.mesh = mesh
        self.fs = fs or LocalFileSystem()

    def _put(self, arr):
        """Row-shard dim 0; multi-process: `arr` is this process's shard."""
        if self.mesh is None:
            return jax.device_put(arr)
        from .parallel.mesh import put_row_sharded

        return put_row_sharded(arr, self.mesh)

    def _put_rep(self, arr):
        return jax.device_put(arr)

    _guard = None  # PreemptionGuard while train() runs (resilience/preempt.py)

    def train(self, ingest: Optional[IngestResult] = None) -> BoostResult:
        # preemption-safe: SIGTERM/SIGINT defer to the next tree boundary;
        # every finished tree is already dumped (tree-%05d + tree-info), so
        # the boundary just exits via Preempted and `--resume auto`
        # continues at the last finished tree (docs/fault_tolerance.md)
        # `train.run`: the root of every span of the run (the CLI has opened
        # it around the data load already; the benchmark enters here)
        with obs_root_span("train.run", family=self.variant), trainer_guard(self):
            return self._train_impl(ingest)

    def _train_impl(self, ingest: Optional[IngestResult] = None) -> BoostResult:
        p = self.params
        t0 = time.time()
        health.install_trace_counters()
        if ingest is None:
            with obs_span("train.load", model=self.variant):
                ingest = DataIngest(p, fs=self.fs).load()
        ds_train = ingest.train
        ds_test = ingest.test
        if self.mesh is not None:
            from .parallel.mesh import equal_row_target

            ds_train = ds_train.pad_rows_to(equal_row_target(ds_train.n, self.mesh))
            ds_test = (
                ds_test.pad_rows_to(equal_row_target(ds_test.n, self.mesh))
                if ds_test else None
            )

        model = GBSTModel(p, ingest.train.dim, self.variant)
        loss_fn = model.loss
        base_score = float(loss_fn.pred2score(p.uniform_base_prediction))
        lr = p.learning_rate
        tree_num = p.tree_num
        g_weight = float(np.sum(ds_train.weight))
        g_weight_test = float(np.sum(ds_test.weight)) if ds_test else 0.0
        if jax.process_count() > 1:
            from .parallel.collectives import host_allgather_objects

            g_weight = float(sum(host_allgather_objects(g_weight)))
            g_weight_test = float(sum(host_allgather_objects(g_weight_test)))

        # one row chunk for the fit and the fold, chosen once. A chunked
        # scan pads its rows to whole chunks. Padded here, once, with
        # zero-weight rows (as a mesh's are), every pass finds whole chunks
        # and pads nothing; padded inside the program, every pass and every
        # fold copies idx and val whole before its scan (2 x 1.2 GB at
        # 10.5M rows x 29)
        width = int(ds_train.idx.shape[1]) if ds_train.idx.ndim > 1 else 1
        n_shards = int(self.mesh.devices.size) if self.mesh is not None else 1
        n_draw = ds_train.n  # the masks' draws keep their count

        def whole_chunks(ds):
            chunk = model.suggest_row_chunk(ds.n, width, n_shards=n_shards)
            if chunk is not None and jax.process_count() == 1:
                ds = ds.pad_rows_to(-(-ds.n // (chunk * n_shards)) * chunk * n_shards)
            return ds, chunk

        ds_train, row_chunk = whole_chunks(ds_train)
        idx = self._put(ds_train.idx)
        val = self._put(ds_train.val)
        y = self._put(ds_train.y)
        weight = self._put(ds_train.weight)
        # padding rows keep weight 0; z starts at the base score
        z = self._put(np.full((ds_train.n,), base_score, np.float32))
        if ds_test is not None:
            ds_test, row_chunk_test = whole_chunks(ds_test)
            idx_t = self._put(ds_test.idx)
            val_t = self._put(ds_test.val)
            y_t = self._put(ds_test.y)
            weight_t = self._put(ds_test.weight)
            z_t = self._put(np.full((ds_test.n,), base_score, np.float32))
            model_t = GBSTModel(
                p, model.n_features, self.variant, dense_ids=_dense_ids(idx_t, val_t)
            )
        # the fit and the train fold evaluate the train rows, the test fold
        # the test rows: each by what its own rows are
        model.dense_ids = _dense_ids(idx, val)

        eval_set = EvalSet(p.loss.evaluate_metric) if p.loss.evaluate_metric else None
        cfg = LBFGSConfig.from_params(p.line_search)

        # what a pass really scans at a time: the rows of a chunk (all of a
        # shard's where nothing is chunked) and the chunks a pass makes, as
        # train.py reports them
        n_rows = int(idx.shape[0])
        shard_rows = -(-n_rows // n_shards)
        chunk_rows = min(row_chunk or shard_rows, shard_rows)
        chunks_per_pass = -(-shard_rows // chunk_rows)
        obs_gauge("blocked.stat.row_chunk", chunk_rows)
        obs_gauge("blocked.stat.chunks_per_pass", chunks_per_pass)
        obs_gauge("blocked.stat.prepared", int(model.prepare is not None))
        obs_gauge("gbst.stat.row_chunk", chunk_rows)
        obs_gauge("gbst.stat.chunks_per_pass", chunks_per_pass)
        obs_gauge("gbst.stat.k", model.K)
        # slots the fit evaluates by the product (0: it looks every slot up)
        obs_gauge(
            "gbst.stat.dense_slots",
            0 if model.dense_ids is None else len(model.dense_ids),
        )
        obs_gauge(
            "gbst.stat.stride",
            model.K - 1 if model.scalar_leaves else 2 * model.K - 1,
        )

        def fold_program(name: str, rows_model: GBSTModel, chunk):
            """`z + lr * tree(w)` over a set of rows, a chunk at a time."""
            out = make_rows(
                rows_model.tree_output, chunk, (True, True, False), self.mesh, "data", 3
            )

            def fold(z, w, idx, val, gate_mask):
                return z + lr * out(w, idx, val, gate_mask)

            fold.__name__ = name
            return Program(fold)

        def gbst_ensemble_loss(s, yy, ww):
            return _ensemble_loss(loss_fn, s, yy, ww)

        fold_train = fold_program("gbst_fold", model, row_chunk)
        jit_ens_loss = Program(gbst_ensemble_loss)
        l1_vec, l2_vec = model.reg_vectors(p.loss.l1[0], p.loss.l2[0])
        # the tree boundary's programs are made here, before the first fit
        w_like = self._put_rep(np.zeros((model.dim,), np.float32))
        full_mask = self._put_rep(np.ones((model.n_features,), np.float32))
        with obs_span("gbst.compile"):
            fold_train.compile(z, w_like, idx, val, full_mask)
            jit_ens_loss.compile(z, y, weight)
            if ds_test is not None:
                fold_test = fold_program("gbst_fold_test", model_t, row_chunk_test)
                fold_test.compile(z_t, w_like, idx_t, val_t, full_mask)
                jit_ens_loss.compile(z_t, y_t, weight_t)

        # continue_train: replay finished trees into z
        # (reference: GBMLRDataFlow.loadModel + per-tree accumulate).
        # Rank0 reads the checkpoints, peers take its broadcast — dumps are
        # rank0-only so non-shared storage must not diverge on resume.
        from .parallel.collectives import load_on_rank0

        finished = 0
        info = load_on_rank0(lambda: model.load_tree_info(self.fs))
        if (p.model.continue_train or p.loss.just_evaluate) and info is not None:
            finished = int(info["finished_tree_num"])
            trees_w = load_on_rank0(
                lambda: [
                    model.load_tree(self.fs, ingest.feature_map, t)
                    for t in range(finished)
                ]
            )
            for t, wt in enumerate(trees_w):
                if wt is None:
                    raise FileNotFoundError(f"tree-{t:05d} missing for continue_train")
                wt = self._put_rep(wt)
                z = fold_train(z, wt, idx, val, full_mask)
                if ds_test is not None:
                    z_t = fold_test(z_t, wt, idx_t, val_t, full_mask)
            log.info("continue_train: replayed %d finished trees", finished)

        # two rng streams: the feature stream draws fixed-size vectors so it
        # stays bitwise-identical across ranks; the instance stream folds in
        # the process index so per-shard sample masks are independent across
        # ranks instead of perfectly correlated (ADVICE r3; process 0 keeps
        # the seed unchanged, so single-process runs reproduce as before)
        rng_inst = np.random.RandomState(
            (p.random.seed + 7919 * jax.process_index()) % (2**32)
        )
        rng_feat = np.random.RandomState(p.random.seed + 104729)
        per_tree_loss: List[float] = []
        compensate = 1.0 / p.instance_sample_rate

        for tree in range(finished, tree_num):
            if self._guard is not None and self._guard.triggered:
                # trees [0, tree) are on disk (dump_tree + tree-info per
                # round) — the dump trail IS the checkpoint
                with obs_span("gbst.preempt", step=tree):
                    self._guard.preempt(
                        p.model.data_path, family=self.variant, trees=tree,
                    )
            with obs_step_span("gbst.tree", tree, tree=tree):
                with obs_span("gbst.masks"):
                    # per-tree Bernoulli masks (reference: randomNextSample)
                    inst = np.zeros((ds_train.n,), np.float32)
                    inst[:n_draw] = rng_inst.rand(n_draw) <= p.instance_sample_rate
                    inst[ds_train.n_real :] = 0.0
                    gmask_np = (
                        rng_feat.rand(model.n_features) <= p.feature_sample_rate
                    ).astype(np.float32)
                    if p.model.need_bias:
                        gmask_np[0] = 1.0
                    gmask = self._put_rep(gmask_np)
                    w_eff = self._put(np.asarray(ds_train.weight) * inst * compensate)
                    w0 = self._put_rep(model.init_weights(tree_seed=tree))

                res = minimize_lbfgs(
                    model.pure_loss,
                    w0,
                    cfg,
                    batch=(idx, val, z, gmask, y, w_eff),
                    l1_vec=l1_vec,
                    l2_vec=l2_vec,
                    g_weight=g_weight,
                    callback=(lambda it, st: True) if p.loss.just_evaluate else None,
                    row_chunk=row_chunk,
                    row_mask=model.batch_row_mask,
                    mesh=self.mesh if row_chunk is not None else None,
                    split=model.loss_split,
                )
                per_tree_loss.append(res.loss / g_weight)
                if p.loss.just_evaluate:
                    break

                # accumulate (reference: GBMLRDataFlow.accumulate — lr-shrunk)
                # and the ensemble's losses, whose fetches settle the span
                w_tree = res.w
                with obs_span("gbst.fold") as sp:
                    z = fold_train(z, w_tree, idx, val, gmask)
                    ens = self._ensemble_scores(z, tree + 1)
                    tl = float(jit_ens_loss(ens, y, weight)) / g_weight
                    sp.add(train_loss=tl)
                    msg = f"ensemble avg loss={tl:.6f}"
                    if ds_test is not None:
                        z_t = fold_test(z_t, w_tree, idx_t, val_t, gmask)
                        ens_t = self._ensemble_scores(z_t, tree + 1)
                        ttl = float(jit_ens_loss(ens_t, y_t, weight_t)) / max(
                            g_weight_test, 1e-12
                        )
                        sp.add(test_loss=ttl)
                        msg += f" test={ttl:.6f}"

                # dump tree + info, rank0-only (reference: dumpModel + dumpModelInfo)
                with obs_span("gbst.dump"):
                    if jax.process_index() == 0:
                        model.dump_tree(
                            self.fs, np.asarray(w_tree), gmask_np, ingest.feature_map, tree
                        )
                        model.dump_tree_info(self.fs, tree + 1, base_score)
                obs_inc("gbst.trees")
                log.info(
                    "[tree=%d] %.1fs fit avg loss=%.6f %s",
                    tree, time.time() - t0, per_tree_loss[-1], msg,
                )

        n_built = max(tree_num - finished, 0) + finished
        ens = self._ensemble_scores(z, max(n_built, 1))
        train_loss = float(jit_ens_loss(ens, y, weight)) / g_weight
        out = BoostResult(
            n_trees=n_built,
            train_loss=train_loss,
            test_loss=None,
            per_tree_loss=per_tree_loss,
        )
        if eval_set is not None:
            out.train_metrics = eval_set.evaluate(
                loss_fn.predict(ens), y, weight
            )
        if ds_test is not None:
            ens_t = self._ensemble_scores(z_t, max(n_built, 1))
            out.test_loss = float(jit_ens_loss(ens_t, y_t, weight_t)) / max(
                g_weight_test, 1e-12
            )
            if eval_set is not None:
                out.test_metrics = eval_set.evaluate(
                    loss_fn.predict(ens_t), y_t, weight_t
                )
        log.info(
            "boosting done: %d trees, train loss %.6f, metrics %s",
            out.n_trees,
            out.train_loss,
            out.train_metrics,
        )
        return out

    def _ensemble_scores(self, z, n_trees: int):
        """GB: z is the ensemble score; RF: averaged (reference (z)/treeNum
        at predict time)."""
        if self.params.gbst_type == "random_forest":
            return z / n_trees
        return z


def _dense_ids(idx, val) -> Optional[np.ndarray]:
    """The one id each slot of these rows holds in every row, or None where
    some slot's differs by row (then every slot is looked up). Processes
    that each see their own rows would have to agree before they trace one
    program: they keep the lookup."""
    if jax.process_count() > 1:
        return None
    ids = constant_slots(idx, val)
    return ids if (ids >= 0).all() else None


def _ensemble_loss(loss_fn, scores, y, weight):
    per_row = jnp.where(weight > 0, loss_fn.loss(scores, y), 0.0)
    return jnp.sum(weight * per_row)
