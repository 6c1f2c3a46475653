"""Training session driver — the TrainWorker + HoagOperation equivalent.

Rebuild of reference worker/TrainWorker.java:133-236 (session setup) +
operation/HoagOperation.java:35-40 (convex outer loop) + the grid
hyper-search rounds of optimizer/HoagOptimizer.java:457-765.

One host process drives the whole mesh: ingest parses text into padded
arrays, rows are device_put sharded over the mesh data axis, and each L-BFGS
iteration runs as a single jitted program (collectives inserted by XLA) —
the reference instead ran slaveNum×threadNum JVM ranks against a CommMaster
rendezvous.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config.params import CommonParams
from .eval import EvalSet
from .io.fs import FileSystem, LocalFileSystem
from .io.reader import DataIngest, IngestResult, SparseDataset
from .models.linear import LinearModel
from .obs import (
    gauge as obs_gauge,
    health,
    inc as obs_inc,
    recorder,
    root_span as obs_root_span,
    span as obs_span,
)
from .obs.scopes import Program
from .optimize import LBFGSConfig, inv_hessian_vp, minimize_lbfgs
from .resilience import trainer_guard

log = logging.getLogger("ytklearn_tpu.train")


@dataclass
class TrainResult:
    w: np.ndarray
    loss: float  # regularized weighted-sum train loss
    avg_loss: float
    pure_loss: float
    test_loss: Optional[float]
    n_iter: int
    status: str
    train_metrics: Dict[str, float] = field(default_factory=dict)
    test_metrics: Dict[str, float] = field(default_factory=dict)
    best_l1: Optional[float] = None
    best_l2: Optional[float] = None
    history: List[Dict] = field(default_factory=list)


class HoagTrainer:
    """Convex-family trainer (linear now; multiclass/FM/FFM plug the same
    surface via their model classes)."""

    def __init__(
        self,
        params: CommonParams,
        model_name: str = "linear",
        mesh=None,
        fs: Optional[FileSystem] = None,
        model_factory: Optional[Callable] = None,
        transform_hook: Optional[Callable] = None,
    ):
        self.params = params
        self.model_name = model_name
        self.mesh = mesh
        self.fs = fs or LocalFileSystem()
        self.model_factory = model_factory
        self.transform_hook = transform_hook

    def _ingest(self) -> IngestResult:
        """Model-aware ingest (reference: DataFlowFactory.createDataFlow:37-72
        — each family has its own dataflow; here only label width and the
        FFM field map differ)."""
        p = self.params
        kwargs = {}
        if self.model_name == "multiclass_linear":
            kwargs["n_labels"] = int(p.k)
        elif self.model_name == "ffm":
            from .models.ffm import load_field_dict

            if not p.model.field_dict_path:
                raise ValueError("ffm requires model.field_dict_path")
            self._field_map = load_field_dict(self.fs, p.model.field_dict_path)
            kwargs["field_map"] = self._field_map
        return DataIngest(
            p, fs=self.fs, transform_hook=self.transform_hook, **kwargs
        ).load()

    def _make_model(self, ingest: IngestResult):
        dim = ingest.train.dim
        if self.model_factory is not None:
            return self.model_factory(self.params, dim)
        if self.model_name == "linear":
            return LinearModel(self.params, dim)
        if self.model_name == "multiclass_linear":
            from .models.multiclass import MulticlassLinearModel

            return MulticlassLinearModel(self.params, dim)
        if self.model_name == "fm":
            from .models.fm import FMModel

            return FMModel(self.params, dim)
        if self.model_name == "ffm":
            from .models.ffm import FFMModel, load_field_dict

            # reuse the dict _ingest loaded so n_fields always matches the
            # field indices baked into ds.field (a caller-supplied ingest
            # must carry the same dict)
            field_map = getattr(self, "_field_map", None) or load_field_dict(
                self.fs, self.params.model.field_dict_path
            )
            return FFMModel(self.params, dim, n_fields=len(field_map))
        raise ValueError(f"unknown model {self.model_name!r}")

    def _device_batch(self, model, ds: SparseDataset) -> Tuple:
        """Build the model's batch and shard rows over the mesh (weights on
        padding rows are 0 so every weighted reduction ignores them).

        Multi-process: `ds` is this process's ingest shard; shards are
        padded to equal length and assembled into one global row-sharded
        array per field (each worker's rows become its device shard)."""
        from .parallel.mesh import equal_row_target, put_row_sharded

        if self.mesh is None:
            host = model.make_batch(ds)
            return tuple(jax.device_put(a) for a in host)
        ds = ds.pad_rows_to(equal_row_target(ds.n, self.mesh))
        host = model.make_batch(ds)
        return tuple(put_row_sharded(a, self.mesh) for a in host)

    _guard = None  # PreemptionGuard while train() runs (resilience/preempt.py)

    def train(self, ingest: Optional[IngestResult] = None) -> TrainResult:
        # preemption-safe: SIGTERM/SIGINT defer to the next L-BFGS
        # iteration callback, which dumps the current weights through the
        # ordinary checkpoint path and raises Preempted; the relaunch
        # resumes as a continue_train warm start (docs/fault_tolerance.md)
        # `train.run`: the root of every span of the run (the benchmark
        # enters here; the CLI has opened it around the data load already)
        with obs_root_span("train.run", family=self.model_name), trainer_guard(self):
            return self._train_impl(ingest)

    def _train_impl(self, ingest: Optional[IngestResult] = None) -> TrainResult:
        p = self.params
        t0 = time.time()
        ts = self.time_stats = {}  # phase counters (data/gbdt/TimeStats.java
        # + TrainWorker.java:209-212 LoadDataFlow/PreprocessAndTrain segments)
        recorder.auto_install()
        recorder.set_config_fingerprint(p)
        health.install_trace_counters()
        if ingest is None:
            with obs_span("train.load", model=self.model_name):
                ingest = self._ingest()
        ts["load"] = time.time() - t0
        health.record_memory("train.load")
        log.info(
            "load flow done in %.1fs: %d train rows, dim %d",
            ts["load"],
            ingest.train.n_real,
            ingest.train.dim,
        )
        model = self._make_model(ingest)

        train_b = self._device_batch(model, ingest.train)
        test_b = self._device_batch(model, ingest.test) if ingest.test else None
        g_weight = float(np.sum(ingest.train.weight))
        g_weight_test = float(np.sum(ingest.test.weight)) if ingest.test else 0.0
        if jax.process_count() > 1:
            # global weight normalizers (reference: CoreData.globalSync
            # weight allreduce)
            from .parallel.collectives import host_allgather_objects

            g_weight = float(sum(host_allgather_objects(g_weight)))
            g_weight_test = float(sum(host_allgather_objects(g_weight_test)))

        # continue_train / just_evaluate warm start (LinearModelDataFlow
        # .loadModel); rank0 reads, every rank warm-starts from its
        # broadcast (dumps are rank0-only; non-shared storage would diverge)
        w0 = None
        if p.model.continue_train or p.loss.just_evaluate:
            from .parallel.collectives import load_on_rank0

            w0 = load_on_rank0(
                lambda: model.load_model(self.fs, ingest.feature_map)
            )
            if w0 is not None:
                log.info("continue_train: loaded existing model")
        if w0 is None:
            w0 = model.init_weights()

        eval_k = max(getattr(model, "n_labels", 1), 2)
        eval_set = (
            EvalSet(p.loss.evaluate_metric, K=eval_k)
            if p.loss.evaluate_metric
            else None
        )
        # blocked evaluation: chunk row arrays so per-row score
        # intermediates (FM/FFM latent gathers) never scale peak memory
        # with n (reference blocked-CoreData contract, CoreData.java:51-52)
        n_rows = int(train_b[0].shape[0])
        width = int(train_b[0].shape[1]) if train_b[0].ndim > 1 else 1
        n_shards = int(self.mesh.devices.size) if self.mesh is not None else 1
        row_chunk = model.suggest_row_chunk(n_rows, width, n_shards=n_shards)
        row_mask = model.batch_row_mask
        # mesh-aware when sharded: chunks stay shard-local (a plain scan on
        # a row-sharded array would all-gather the batch onto every device)
        from .optimize.blocked import make_rows, make_sum, make_value_and_grad

        if row_chunk is not None:
            log.info("blocked evaluation: row chunk %d", row_chunk)
        # what a pass really scans at a time: the rows of a chunk (all of a
        # shard's where nothing is chunked) and the chunks a pass makes
        shard_rows = -(-n_rows // n_shards)
        chunk_rows = min(row_chunk or shard_rows, shard_rows)
        obs_gauge("blocked.stat.row_chunk", chunk_rows)
        obs_gauge("blocked.stat.chunks_per_pass", -(-shard_rows // chunk_rows))
        # 1: the model declares its parameter-only work (`prepare`) and a
        # chunked pass does it once, outside the scan; 0: it declares none
        obs_gauge("blocked.stat.prepared", int(model.prepare is not None))
        nb = len(train_b)
        sum_loss = make_sum(
            model.pure_loss, row_chunk, row_mask, self.mesh, "data", nb,
            split=model.loss_split,
        )
        rows_predict = make_rows(
            model.predicts, row_chunk, row_mask, self.mesh, "data", nb,
            split=model.predicts_split,
        )

        # the two evaluation programs under names of their own
        # (`jit_eval_loss`, `jit_eval_predicts` on a device trace)
        def eval_loss(w, *batch):
            return sum_loss(w, *batch)

        def eval_predicts(w, *batch):
            return rows_predict(w, *batch)

        jit_loss = Program(eval_loss)
        jit_predicts = Program(eval_predicts)
        jit_precision = (
            jax.jit(model.precision) if hasattr(model, "precision") else None
        )

        def evaluate(w, results_sink: Dict) -> None:
            if eval_set is not None:
                with obs_span("train.evaluate"):
                    results_sink["train_metrics"] = eval_set.evaluate(
                        jit_predicts(w, *train_b), train_b[-2], train_b[-1]
                    )
                    if test_b is not None:
                        results_sink["test_metrics"] = eval_set.evaluate(
                            jit_predicts(w, *test_b), test_b[-2], test_b[-1]
                        )

        # hyper-search (reference grid rounds :457-765 / HOAG :813-902) or
        # a single run
        hoag_mode = p.hyper.switch_on and p.hyper.mode == "hoag"
        if p.hyper.switch_on and p.hyper.mode == "grid":
            l1_grid = p.hyper.grid_l1 or [p.loss.l1[0]]
            l2_grid = p.hyper.grid_l2 or [p.loss.l2[0]]
            rounds = [(a, b) for a in l1_grid for b in l2_grid]
        elif hoag_mode:
            if test_b is None:
                raise ValueError(
                    "hyper.mode=hoag needs test data (data.test.data_path): the "
                    "hypergradient is the test-loss gradient"
                )
            n_blocks = len(model.regular_blocks())
            hoag_l1 = np.broadcast_to(
                np.atleast_1d(np.asarray(p.hyper.hoag_l1, float)), (n_blocks,)
            ).copy()
            hoag_l2 = np.broadcast_to(
                np.atleast_1d(np.asarray(p.hyper.hoag_l2, float)), (n_blocks,)
            ).copy()
            if p.hyper.hoag_outer_iter <= 0:
                raise ValueError(
                    f"hyper.hoag.outer_iter must be > 0, got {p.hyper.hoag_outer_iter}"
                )
            if not np.any(hoag_l2 > 0.0):
                raise ValueError(
                    "hyper.mode=hoag needs at least one positive hyper.hoag.l2 "
                    "entry (the hypergradient steps log(l2); l2=0 blocks are "
                    "held fixed)"
                )
            rounds = [(hoag_l1, hoag_l2)] * p.hyper.hoag_outer_iter
            hoag_steps = np.full((n_blocks,), p.hyper.hoag_init_step)
            hoag_grad_hist: List[np.ndarray] = []
            hoag_delta_hist: List[float] = []
            hoag_t_old = 0.0
            _cvg = make_value_and_grad(
                model.pure_loss, row_chunk, row_mask, self.mesh, "data",
                len(test_b), split=model.loss_split,
            )
            jit_grad_test = jax.jit(lambda w, *b: _cvg(w, *b)[1])
        else:
            if p.hyper.switch_on:
                log.warning(
                    "unknown hyper.mode=%r (grid|hoag); running a single round "
                    "at l1=%g l2=%g",
                    p.hyper.mode,
                    p.loss.l1[0],
                    p.loss.l2[0],
                )
            rounds = [(p.loss.l1[0], p.loss.l2[0])]

        cfg = LBFGSConfig.from_params(p.line_search)
        best = None  # (test_loss, result, l1, l2)
        history: List[Dict] = []

        # restart=True: every round restores the *initial* w (incl. any
        # continue_train warm start); restart=False: rounds carry the
        # previous round's solution (reference: HoagOptimizer.java:318,469)
        carry_w = w0
        for round_idx in range(len(rounds)):
            l1, l2 = (hoag_l1, hoag_l2) if hoag_mode else rounds[round_idx]
            l1_vec, l2_vec = model.reg_vectors(l1, l2)
            start_w = w0 if p.hyper.restart else carry_w
            # convex-loop sentinel on the TEST loss — the signal the
            # lbfgs-internal sentinels can't see (they own the train loss;
            # guarding both here would double-count every incident)
            guard = health.ProgressGuard("train.convex_test", window=12)

            def callback(it, state):
                # the whole host callback of an iteration under one span
                # of that step: test loss, metrics, log, dumps
                with obs_span("train.callback", step=it):
                    return host_callback(it, state)

            def host_callback(
                it, state, _l1=l1, _l2=l2, _l1v=l1_vec, _l2v=l2_vec, _guard=guard
            ):
                rec = {
                    "iter": it,
                    "l1": _l1,
                    "l2": _l2,
                    "loss": float(state.loss),
                    "avg_loss": float(state.loss) / g_weight,
                    "pure_loss": float(state.pure_loss),
                }
                if test_b is not None:
                    with obs_span("train.test_loss"):  # settled by its float()
                        rec["test_loss"] = float(jit_loss(state.w, *test_b)) / max(
                            g_weight_test, 1e-12
                        )
                if health.enabled() and "test_loss" in rec:
                    health.check_loss("train.convex_test", rec["test_loss"], iter=it)
                    _guard.update(rec["test_loss"], iter=it)
                if it % 5 == 0 or it <= 1:
                    evaluate(state.w, rec)
                history.append(rec)
                log.info(
                    "[iter=%d] %.1fs train avg loss=%.6f%s",
                    it,
                    time.time() - t0,
                    rec["avg_loss"],
                    f" test avg loss={rec['test_loss']:.6f}" if "test_loss" in rec else "",
                )
                if self._guard is not None and self._guard.triggered:
                    # iteration boundary = the convex safe point: dump the
                    # current weights (the L-BFGS checkpoint the relaunch
                    # warm-starts from) and exit via Preempted — checked
                    # BEFORE the periodic dump so the grace window never
                    # pays for the same serialization twice
                    self._dump(
                        model, state.w, ingest, _l2v, g_weight, train_b,
                        jit_precision,
                    )
                    self._guard.preempt(
                        p.model.data_path, family=self.model_name,
                        iteration=it,
                    )
                # periodic checkpoint (reference dump_freq block :647-660)
                if p.model.dump_freq > 0 and it > 0 and it % p.model.dump_freq == 0:
                    self._dump(
                        model, state.w, ingest, _l2v, g_weight, train_b, jit_precision
                    )
                if p.loss.just_evaluate:
                    return True
                return False

            obs_inc("train.rounds")
            with obs_span("train.round", round=round_idx):
                res = minimize_lbfgs(
                    model.pure_loss,
                    jnp.asarray(start_w, jnp.float32),
                    cfg,
                    batch=train_b,
                    l1_vec=l1_vec,
                    l2_vec=l2_vec,
                    g_weight=g_weight,
                    callback=callback,
                    row_chunk=row_chunk,
                    row_mask=row_mask,
                    mesh=self.mesh if row_chunk is not None else None,
                    split=model.loss_split,
                )
            carry_w = np.asarray(res.w)
            # round selection: test loss when available, else the *pure*
            # train loss — the regularized loss would always prefer the
            # smallest penalty (reference compares test loss, :489-500).
            # In HOAG mode the final round wins (reference dumps the last w).
            tl = (
                float(jit_loss(res.w, *test_b)) if test_b is not None else res.pure_loss
            )
            if best is None or hoag_mode or tl < best[0]:
                best = (tl, res, l1, l2)
            if len(rounds) > 1:
                log.info(
                    "[hyper l1=%s l2=%s] train loss %.6f test loss %s",
                    np.asarray(l1),
                    np.asarray(l2),
                    res.loss / g_weight,
                    tl / max(g_weight_test, 1e-12) if test_b is not None else "n/a",
                )

            if hoag_mode:
                # ---- HOAG hypergradient step on log λ₂ (reference:
                # HoagOptimizer.hyperHoagOptimization:813-902) ----
                tl_avg = tl / max(g_weight_test, 1e-12)
                gtest = jit_grad_test(res.w, *test_b) / g_weight_test
                q = np.asarray(inv_hessian_vp(res.state, gtest, cfg.m))
                w_np = np.asarray(res.w)
                grad_log_l2 = np.zeros_like(hoag_l2)
                for r, (s, e) in enumerate(model.regular_blocks()):
                    if hoag_l2[r] > 0.0:
                        grad_log_l2[r] = (
                            -hoag_l2[r] * g_weight * float(np.dot(w_np[s:e], q[s:e]))
                        )
                hoag_delta_hist.append(tl_avg - hoag_t_old)
                hoag_t_old = tl_avg
                hoag_grad_hist.append(grad_log_l2)
                # step shrink on hypergradient sign flip (:845-857)
                if len(hoag_grad_hist) >= 2:
                    prev = hoag_grad_hist[-2]
                    flip = prev * grad_log_l2 < 0.0
                    hoag_steps = np.where(
                        flip & (hoag_l2 > 0.0),
                        hoag_steps * p.hyper.hoag_step_decr_factor,
                        hoag_steps,
                    )
                # stop when the last-3 average |Δtest loss| stalls (:860-876)
                if len(hoag_delta_hist) >= 3:
                    sumdelta = float(np.mean(np.abs(hoag_delta_hist[-3:])))
                    if sumdelta < p.hyper.hoag_test_loss_reduce_limit:
                        log.info(
                            "[hoag] last 3 avg test loss delta %.3g < %g, exit! "
                            "final l2: %s",
                            sumdelta,
                            p.hyper.hoag_test_loss_reduce_limit,
                            hoag_l2,
                        )
                        break
                # signed step on log λ₂ (:885-895)
                upd = hoag_l2 > 0.0
                logl2 = np.where(upd, np.log(np.where(upd, hoag_l2, 1.0)), 0.0)
                logl2 = logl2 + np.where(-grad_log_l2 >= 0.0, hoag_steps, -hoag_steps)
                hoag_l2 = np.where(upd, np.exp(logl2), hoag_l2)
                log.info(
                    "[hoag round %d] test avg loss %.6f hypergrad %s new l2 %s",
                    round_idx,
                    tl_avg,
                    grad_log_l2,
                    hoag_l2,
                )

        tl, res, bl1, bl2 = best
        _, l2_vec = model.reg_vectors(bl1, bl2)
        self._dump(model, res.w, ingest, l2_vec, g_weight, train_b, jit_precision)

        out = TrainResult(
            w=np.asarray(res.w),
            loss=res.loss,
            avg_loss=res.loss / g_weight,
            pure_loss=res.pure_loss,
            test_loss=(tl / max(g_weight_test, 1e-12)) if test_b is not None else None,
            n_iter=res.n_iter,
            status=res.status,
            best_l1=bl1,
            best_l2=bl2,
            history=history,
        )
        sink: Dict = {}
        evaluate(res.w, sink)
        out.train_metrics = sink.get("train_metrics", {})
        out.test_metrics = sink.get("test_metrics", {})
        ts["train"] = time.time() - t0 - ts["load"]
        health.record_memory("train.train")
        if res.n_iter > 0 and ts["train"] > 0:
            ts["iters_per_sec"] = res.n_iter / ts["train"]
        # phase stats mirrored into the obs registry (one source of truth
        # for bench/report surfaces; time_stats stays the in-process view)
        for k, v in ts.items():
            obs_gauge(f"train.phase.{k}", v)
        obs_inc("train.iterations_total", res.n_iter)
        log.info(
            "training done: %s after %d iters, avg loss %.6f, metrics %s",
            res.status,
            res.n_iter,
            out.avg_loss,
            out.train_metrics,
        )
        log.info(
            "[time stats] load=%.1fs train=%.1fs%s",
            ts["load"], ts["train"],
            (
                f" rate={ts['iters_per_sec']:.2f} iters/s"
                if "iters_per_sec" in ts else ""
            ),
        )
        return out

    def _dump(
        self, model, w, ingest, l2_vec, g_weight, train_b, jit_precision=None
    ) -> None:
        with obs_span("train.dump"):
            precision = None
            if jit_precision is not None:
                precision = np.asarray(
                    jit_precision(w, *train_b, l2_vec=l2_vec, g_weight=g_weight)
                )
            if jax.process_index() != 0:
                return  # rank0-only dump (reference: HoagOptimizer.java:647-660)
            model.dump_model(self.fs, np.asarray(w), precision, ingest.feature_map)
