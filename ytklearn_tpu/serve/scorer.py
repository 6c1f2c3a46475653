"""CompiledScorer — lower a loaded OnlinePredictor into jitted batch kernels.

The predictor side-stack (predict/) walks name-keyed hash maps per sample on
the host: correct, thread-safe, and ~1k req/s. Serving throughput comes from
the XGBoost/Clipper lesson — amortize per-request overhead into fixed-shape
batches — which on TPU additionally means a *bucketed-shape ladder*: requests
are padded up to the smallest compiled rung (default 1/8/64/512, knob
YTK_SERVE_LADDER), so mixed request sizes hit at most len(ladder) XLA
compilations, all of them at warmup. The r8 RetraceSentinel watches the
steady state; a post-warmup compile fires `health.retrace`.

Lowering per family (model maps -> dense arrays, request dicts -> rows):

  linear            score = X @ w + bias
  multiclass_linear scores = [X @ W + b, 0]
  fm                wx + 1/2 Σ_k[(X V)² − X² V²]; bias rides as an x=1 column
  ffm               field-aware pairwise terms via a (B,F,F,k) field-block
                    einsum (exactly the Σ_{p<q} host sum, closed form)
  gbdt              stacked node arrays, fixed-depth vectorized traversal;
                    accumulation runs tree-ascending in float64, so scores
                    are BIT-IDENTICAL to OnlinePredictor.batch_scores
                    (scripts/serve_bench.py asserts this)
  gbmlr/gbsdt/...   stacked per-tree expert/gate matrices, softmax or
                    heap-sigmoid gating

Host featurization runs the shared TransformPipeline (transform/) — vector
assembly against the model vocab, murmur hashing with signed collision
accumulation, missing fill, and transform-stat replay as ONE numpy batch
stage per micro-batch (the `serve.transform` trace hop) instead of a
per-scalar host loop. It is the same implementation the trainers' ingest
and the offline predictors execute, so a served request sees bit-for-bit
the same feature pipeline as the offline path by construction.
Sample-dependent base predictions (`other`) are an offline concept and not
supported here.
"""

from __future__ import annotations

import contextlib
import logging
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import knobs
from ..obs import health as obs_health
from ..obs import event as obs_event, inc as obs_inc, span as obs_span
from ..obs import profiler
from ..obs import trace as obs_trace
from ..predict.base import OnlinePredictor, numpy_activation
from ..predict.continuous import (
    FFMPredictor,
    FMPredictor,
    LinearPredictor,
    MulticlassLinearPredictor,
)
from ..predict.trees import GBDTPredictor, GBSTPredictor
from ..transform.pipeline import TransformPipeline

log = logging.getLogger(__name__)

DEFAULT_LADDER = (1, 8, 64, 512)

#: XLA compiles attributed to scorer warmups (process-wide, GIL-guarded).
#: The retrace sentinel watches a process-GLOBAL compile counter; without
#: this credit, warming a replacement scorer (hot reload) or a second
#: model would falsely fire health.retrace on every already-armed scorer.
#: While a warmup is IN PROGRESS its compiles have landed in the global
#: counter but not yet in the credit, so armed scorers skip checks for the
#: duration and re-baseline on their next batch (_warmups_in_progress).
_warmup_compile_credit = 0.0
_warmups_in_progress = 0


class _LadderRetraceSentinel(obs_health.RetraceSentinel):
    """RetraceSentinel that discounts compiles other scorers' warmups did."""

    @staticmethod
    def _compiles() -> float:
        return obs_health.RetraceSentinel._compiles() - _warmup_compile_credit


@contextlib.contextmanager
def compile_credit():
    """Attribute every XLA compile inside the block to a known-good cause
    so armed scorers don't count them as steady-state serving retraces.
    Used by scorer warmups, and by the continual retrain driver when a
    candidate trains IN-PROCESS next to live serving (docs/continual.md):
    training compiles are expected, a /predict-path compile still is not."""
    global _warmup_compile_credit, _warmups_in_progress
    before = obs_health.RetraceSentinel._compiles()
    _warmups_in_progress += 1
    try:
        yield
    finally:
        # credit BEFORE dropping the in-progress flag, so once the flag
        # clears the subtraction is already settled
        _warmup_compile_credit += (
            obs_health.RetraceSentinel._compiles() - before
        )
        _warmups_in_progress -= 1


def parse_ladder(spec: Optional[str] = None) -> Tuple[int, ...]:
    """YTK_SERVE_LADDER="1,8,64,512" -> sorted unique rung tuple."""
    if spec is None:
        spec = knobs.get_str("YTK_SERVE_LADDER") or ""
    if not spec:
        return DEFAULT_LADDER
    rungs = sorted({int(v) for v in str(spec).split(",") if v.strip()})
    if not rungs or rungs[0] < 1:
        raise ValueError(f"bad serve ladder {spec!r}: rungs must be >= 1")
    return tuple(rungs)


def resolve_mode() -> str:
    """Requested GBDT scoring rung from the knobs: binned wins over fused
    (it subsumes it — integer compares through the same fused layouts),
    default is the bit-identity stacked path."""
    if knobs.get_bool("YTK_SERVE_BINNED"):
        return "binned"
    if knobs.get_bool("YTK_SERVE_FUSED"):
        return "fused"
    return "stacked"


class CompiledScorer:
    """Batch scorer for one loaded model; thread-safe after construction
    (score paths touch only immutable arrays + jit caches).

    GBDT execution rungs (docs/serving.md "Precision rungs"): the default
    `stacked` path keeps the bit-identity contract; `mode="fused"` routes
    through the Pallas heap-traversal kernel (serve/kernels.py) and
    `mode="binned"` additionally scores from uint8/uint16 bin indices
    (dumped training edges, else ensemble thresholds) on the fastest
    available backend (Pallas on TPU, the native C++ kernel on CPU, an
    XLA packed walk everywhere). Every fallback is a named
    `serve.downgrade.*` counter + event — a Mosaic/toolchain failure
    costs throughput, never the server. `precision="bf16"` relaxes the
    convex/FM/FFM einsum accumulations to bf16 inputs with f32
    accumulation (quality bands measured in scripts/serve_bench.py)."""

    def __init__(
        self,
        predictor: OnlinePredictor,
        ladder: Optional[Sequence[int]] = None,
        warmup: bool = True,
        mode: Optional[str] = None,
        precision: Optional[str] = None,
        fused_interpret: bool = False,
    ):
        import jax

        self.predictor = predictor
        self.ladder = tuple(sorted(set(ladder))) if ladder else parse_ladder()
        self.n_outputs = predictor.n_outputs
        self.requested_mode = mode if mode is not None else resolve_mode()
        if self.requested_mode not in ("stacked", "fused", "binned"):
            raise ValueError(f"unknown serve mode {self.requested_mode!r}")
        self.precision = (
            precision
            if precision is not None
            else (knobs.get_str("YTK_SERVE_PRECISION") or "f64")
        )
        if self.precision not in ("f64", "bf16"):
            raise ValueError(f"unknown serve precision {self.precision!r}")
        self.mode = "stacked"  # effective; rung lowering may upgrade it
        self.backend = "stacked-xla"
        self.bin_mode: Optional[str] = None
        self.bin_dtype: Optional[str] = None
        self._fused_interpret = fused_interpret
        self._fill = 0.0  # pad/absent-feature value; NaN for gbdt (missing)
        self._bias_col: Optional[int] = None
        self._exec = None  # non-jit execution override (binned backends)
        self._prep_is_identity = False  # gbdt: rows pass through untransformed
        self._lower()
        self.dim = len(self.vocab) + (1 if self._bias_col is not None else 0)
        # the shared batched featurize path (transform/pipeline.py):
        # identity assembly for gbdt (raw values, NaN missing-fill), the
        # full bias-drop -> hash -> assemble -> replay stage for the
        # _prep families — one implementation with ingest and predict
        if self._prep_is_identity:
            self._pipeline = TransformPipeline.for_identity(
                self.vocab, self.dim, fill=self._fill
            )
        else:
            pp = predictor.params
            self._pipeline = TransformPipeline(
                vocab=self.vocab,
                dim=self.dim,
                bias_col=self._bias_col,
                fill=self._fill,
                bias_name=pp.model.bias_feature_name,
                feature_hash=predictor.feature_hash,
                nodes=predictor.transform_nodes,
                transform_on=pp.feature.transform.switch_on,
            )
        self._jit = jax.jit(self._kernel)
        if self._exec is None:
            self._exec = self._exec_jit
        # post-warmup compiles are a bug (the ladder exists to prevent
        # them); the sentinel makes one fire health.retrace loudly
        obs_health.install_trace_counters()
        self._sentinel = _LadderRetraceSentinel("serve.scorer")
        self._warm = False
        self._rearm_pending = False
        # ytkprof per-rung attribution: settled execute seconds + row
        # counts per ladder rung (written only when the plane is on; read
        # by /metrics?prof=1 via prof_snapshot)
        self._prof_lock = threading.Lock()
        self._rung_stats: Dict[int, dict] = {}
        if warmup:
            self.warmup()

    # -- public API -------------------------------------------------------

    def warmup(self) -> None:
        """Compile every ladder rung now (load time), then arm the retrace
        sentinel — steady-state traffic must never compile again. The
        compiles this causes are credited so scorers already armed (hot
        reload warms the replacement while the old one still serves) don't
        count them as steady-state retraces."""
        with compile_credit():
            with obs_span("serve.warmup", rungs=len(self.ladder)):
                for rung in self.ladder:
                    X = np.full((rung, self.dim), self._fill, np.float64)
                    # ledger label (no-op unless ytkprof is on): the rung
                    # compiles land named with their batch signature, so
                    # a later steady-state retrace's culprit diff reads
                    # "serve.rung.64: float64[64,D] -> ..." instead of
                    # "<unlabeled>"
                    with profiler.LEDGER.program(
                        "serve.rung.%d" % rung,
                        sig_fn=lambda x=X: profiler.abstract_signature(x),
                    ):
                        self._exec(X)  # blocks: compile+execute now
                    obs_inc("serve.scorer.warmup_rungs")
        self._sentinel.arm()
        self._warm = True

    def rung_info(self) -> Dict[str, object]:
        """The effective scoring rung — bench/metrics evidence."""
        info = {
            "requested": self.requested_mode,
            "mode": self.mode,
            "backend": self.backend,
            "precision": self.precision,
            "downgraded": self.mode != self.requested_mode,
        }
        if self.bin_mode is not None:
            info["bin_mode"] = self.bin_mode
            info["bin_dtype"] = self.bin_dtype
        return info

    def featurize(self, rows: Sequence[Dict[str, float]]) -> np.ndarray:
        """Request dicts -> dense (B, dim) float64 via the shared batched
        pipeline (transform/pipeline.py): hash + transform replay for the
        _prep families, raw values with NaN fill for gbdt. The transform
        stage gets its own `serve.transform` hop nested inside
        `serve.assemble` so ytkprof can split assembly cost from the
        hash/replay cost."""
        pipe = self._pipeline
        if pipe.identity:
            # gbdt identity assembly: no hashing, no stat replay — the
            # hop would only measure the scatter serve.assemble already
            # covers
            return pipe.featurize(rows)
        with obs_trace.batch_hop("serve.transform", rows=len(rows)):
            return pipe.featurize(rows)

    def score_batch(self, rows: Sequence[Dict[str, float]]) -> np.ndarray:
        """Raw scores, shape (B,) or (B, K) — the batch_scores contract."""
        return self._run(rows)[0]

    def predict_batch(self, rows: Sequence[Dict[str, float]]) -> np.ndarray:
        """Activated predictions (loss.predict applied in-kernel)."""
        return self._run(rows)[1]

    def score_and_predict(
        self, rows: Sequence[Dict[str, float]]
    ) -> Tuple[np.ndarray, np.ndarray]:
        return self._run(rows)

    # -- execution --------------------------------------------------------

    def _rung_for(self, n: int) -> int:
        for r in self.ladder:
            if r >= n:
                return r
        return self.ladder[-1]

    def _exec_jit(self, chunk: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        # host<->device hops at the jit boundary are EXPLICIT (jnp.asarray
        # in, device_get out): the --ytk-sanitize transfer guard proves the
        # steady-state score path performs no hidden implicit transfer
        import jax
        import jax.numpy as jnp

        return jax.device_get(self._jit(jnp.asarray(chunk)))

    def _run(self, rows) -> Tuple[np.ndarray, np.ndarray]:
        # batch assembly hop: request dicts -> dense matrix. batch_hop is
        # the cached no-op unless the surrounding micro-batch carries a
        # sampled request trace (obs/trace.py)
        with obs_trace.batch_hop("serve.assemble", rows=len(rows)):
            X = self.featurize(rows)
        B = X.shape[0]
        prof_on = profiler.enabled()  # one check per batch, not per chunk
        max_rung = self.ladder[-1]
        out_s: List[np.ndarray] = []
        out_p: List[np.ndarray] = []
        for start in range(0, max(B, 1), max_rung):
            chunk = X[start : start + max_rung]
            if chunk.shape[0] == 0:
                break
            rung = self._rung_for(chunk.shape[0])
            pad = rung - chunk.shape[0]
            if pad:
                chunk = np.concatenate(
                    [chunk, np.full((pad, self.dim), self._fill, np.float64)]
                )
            with obs_span("serve.score", rung=rung, rows=rung - pad):
                # ladder-rung execution hop, tagged with the EFFECTIVE
                # rung (mode/backend from rung_info — a downgraded fused
                # rung shows up as stacked in the trace, honestly)
                with obs_trace.batch_hop(
                    "serve.execute", rung=rung, mode=self.mode,
                    backend=self.backend,
                ):
                    if prof_on:
                        # settled per-rung attribution: _exec device_gets,
                        # so this wall interval IS the rung's kernel+copy
                        # time; any compile inside lands named in the
                        # ledger with the chunk signature
                        t_exec = time.perf_counter()
                        with profiler.LEDGER.program(
                            "serve.rung.%d" % rung,
                            sig_fn=lambda c=chunk: (
                                profiler.abstract_signature(c)
                            ),
                        ):
                            s, p = self._exec(chunk)
                        self._note_rung(
                            rung, rung - pad, time.perf_counter() - t_exec
                        )
                    else:
                        s, p = self._exec(chunk)
            obs_inc("serve.scorer.batches")
            obs_inc("serve.scorer.rows", rung - pad)
            obs_inc("serve.scorer.pad_rows", pad)
            out_s.append(s[: rung - pad])
            out_p.append(p[: rung - pad])
        if self._warm:
            if _warmups_in_progress:
                # another scorer is mid-warmup: its compiles are in the
                # global counter but not yet credited — don't judge, and
                # take a fresh baseline once the dust settles
                self._rearm_pending = True
            elif self._rearm_pending:
                self._sentinel.arm()
                self._rearm_pending = False
            else:
                self._sentinel.check(rows=B)
        if not out_s:
            shape = (0,) if self.n_outputs == 1 else (0, self.n_outputs)
            return np.empty(shape, np.float64), np.empty(shape, np.float64)
        return np.concatenate(out_s), np.concatenate(out_p)

    def _note_rung(self, rung: int, rows: int, exec_s: float) -> None:
        with self._prof_lock:
            st = self._rung_stats.get(rung)
            if st is None:
                st = self._rung_stats[rung] = {
                    "calls": 0, "rows": 0, "exec_s": 0.0,
                }
            st["calls"] += 1
            st["rows"] += rows
            st["exec_s"] += exec_s

    def prof_snapshot(self) -> dict:
        """Per-rung settled execute-time attribution (ytkprof; the
        `/metrics?prof=1` export). Empty rungs dict when the plane was
        never on — the closing of the r16 "tuned blind" gap: each ladder
        rung reports its device-settled seconds, calls, real rows, and
        derived per-row cost so a mis-tuned ladder is visible in numbers."""
        with self._prof_lock:
            rungs = {
                str(r): {
                    "calls": v["calls"],
                    "rows": v["rows"],
                    "exec_s": round(v["exec_s"], 6),
                    "ms_per_row": (
                        round(1000.0 * v["exec_s"] / v["rows"], 6)
                        if v["rows"] else None
                    ),
                }
                for r, v in sorted(self._rung_stats.items())
            }
        return {
            "mode": self.mode,
            "backend": self.backend,
            "ladder": list(self.ladder),
            "rungs": rungs,
        }

    # -- lowering ---------------------------------------------------------

    def _lower(self) -> None:
        pred = self.predictor
        if not isinstance(pred, GBDTPredictor):
            # fused/binned are GBDT traversal rungs; the einsum families
            # take their own kernels (optionally at the bf16 rung), so a
            # fleet-wide YTK_SERVE_BINNED=1 is not a "downgrade" here
            self.requested_mode = "stacked"
        if isinstance(pred, LinearPredictor):
            self._lower_linear()
        elif isinstance(pred, MulticlassLinearPredictor):
            self._lower_multiclass()
        elif isinstance(pred, FMPredictor):
            self._lower_fm()
        elif isinstance(pred, FFMPredictor):
            self._lower_ffm()
        elif isinstance(pred, GBDTPredictor):
            self._lower_gbdt()
        elif isinstance(pred, GBSTPredictor):
            self._lower_gbst()
        else:
            raise TypeError(
                f"no compiled lowering for {type(pred).__name__}"
            )

    def _continuous_vocab(self, names) -> None:
        """Shared vocab + bias-column plumbing for the _prep families."""
        pred = self.predictor
        bias_name = pred.params.model.bias_feature_name
        self.vocab = {n: i for i, n in enumerate(sorted(names))}
        self._prep = pred._prep
        if pred.params.model.need_bias and bias_name in pred.model_map:
            self._bias_col = len(self.vocab)
            self._bias_name = bias_name
        else:
            self._bias_col = None

    def _act(self):
        """loss.predict as an in-kernel activation closure."""
        loss = self.predictor.loss
        return loss.predict

    def _lower_linear(self) -> None:
        pred = self.predictor
        bias_name = pred.params.model.bias_feature_name
        self._continuous_vocab(n for n in pred.model_map if n != bias_name)
        D = len(self.vocab) + (1 if self._bias_col is not None else 0)
        w = np.zeros(D, np.float64)
        for n, j in self.vocab.items():
            w[j] = pred.model_map[n][0]
        if self._bias_col is not None:
            w[self._bias_col] = pred.model_map[bias_name][0]
        act = self._act()

        if self.precision == "bf16":
            import jax.numpy as jnp

            w16 = jnp.asarray(w, jnp.bfloat16)

            def kernel(X):
                # bf16 operands, f32 accumulation (the MXU contract);
                # quality band measured in scripts/serve_bench.py
                s = jnp.matmul(
                    X.astype(jnp.bfloat16), w16,
                    preferred_element_type=jnp.float32,
                ).astype(X.dtype)
                return s, act(s)
        else:

            def kernel(X):
                s = X @ w
                return s, act(s)

        self._kernel = kernel

    def _lower_multiclass(self) -> None:
        import jax.numpy as jnp

        pred = self.predictor
        bias_name = pred.params.model.bias_feature_name
        self._continuous_vocab(n for n in pred.model_map if n != bias_name)
        K = pred.K
        D = len(self.vocab) + (1 if self._bias_col is not None else 0)
        W = np.zeros((D, K - 1), np.float64)
        for n, j in self.vocab.items():
            W[j] = pred.model_map[n]
        if self._bias_col is not None:
            W[self._bias_col] = pred.model_map[bias_name]
        act = self._act()

        if self.precision == "bf16":
            W16 = jnp.asarray(W, jnp.bfloat16)

            def kernel(X):
                s = jnp.matmul(
                    X.astype(jnp.bfloat16), W16,
                    preferred_element_type=jnp.float32,
                ).astype(X.dtype)
                s = jnp.concatenate(
                    [s, jnp.zeros((X.shape[0], 1), s.dtype)], axis=-1
                )
                return s, act(s)
        else:

            def kernel(X):
                s = X @ W
                s = jnp.concatenate(
                    [s, jnp.zeros((X.shape[0], 1), s.dtype)], axis=-1
                )
                return s, act(s)

        self._kernel = kernel

    def _lower_fm(self) -> None:
        import jax.numpy as jnp

        pred = self.predictor
        bias_name = pred.params.model.bias_feature_name
        self._continuous_vocab(n for n in pred.model_map if n != bias_name)
        k = pred.sok
        D = len(self.vocab) + (1 if self._bias_col is not None else 0)
        w = np.zeros(D, np.float64)
        V = np.zeros((D, k), np.float64)
        for n, j in self.vocab.items():
            row = pred.model_map[n]
            if pred.need_first_order:
                w[j] = row[0]
            V[j] = row[1 : 1 + k]
        if self._bias_col is not None:
            # bias adds its weight + latent row at x=1 regardless of the
            # first-order flag (FMOnlinePredictor semantics)
            row = pred.model_map[bias_name]
            w[self._bias_col] = row[0]
            V[self._bias_col] = row[1 : 1 + k]
        act = self._act()

        if self.precision == "bf16":
            w16 = jnp.asarray(w, jnp.bfloat16)
            V16 = jnp.asarray(V, jnp.bfloat16)
            V216 = jnp.asarray(V * V, jnp.bfloat16)

            def kernel(X):
                X16 = X.astype(jnp.bfloat16)
                f32 = jnp.float32
                S = jnp.matmul(X16, V16, preferred_element_type=f32)
                S2 = jnp.matmul(X16 * X16, V216, preferred_element_type=f32)
                wx = jnp.matmul(X16, w16, preferred_element_type=f32)
                s = (wx + 0.5 * jnp.sum(S * S - S2, axis=-1)).astype(X.dtype)
                return s, act(s)
        else:

            def kernel(X):
                S = X @ V
                S2 = (X * X) @ (V * V)
                s = X @ w + 0.5 * jnp.sum(S * S - S2, axis=-1)
                return s, act(s)

        self._kernel = kernel

    def _lower_ffm(self) -> None:
        import jax.numpy as jnp

        pred = self.predictor
        bias_name = pred.params.model.bias_feature_name
        # unknown-field features are dropped entirely at serve time too
        names = [
            n
            for n in pred.model_map
            if n != bias_name and pred._field_of(n) >= 0
        ]
        self._continuous_vocab(names)
        k, F = pred.sok, pred.n_fields
        D = len(self.vocab) + (1 if self._bias_col is not None else 0)
        w = np.zeros(D, np.float64)
        V = np.zeros((D, F, k), np.float64)
        field_idx = np.zeros(D, np.int32)
        for n, j in self.vocab.items():
            row = pred.model_map[n]
            if pred.need_first_order:
                w[j] = row[0]
            V[j] = row[1 : 1 + F * k].reshape(F, k)
            field_idx[j] = pred._field_of(n)
        if self._bias_col is not None:
            row = pred.model_map[bias_name]
            w[self._bias_col] = row[0]
            if k > 0:
                V[self._bias_col] = row[1 : 1 + F * k].reshape(F, k)
            field_idx[self._bias_col] = 0  # bias rides as a field-0, x=1 row
        M = np.zeros((D, F), np.float64)
        M[np.arange(D), field_idx] = 1.0
        # per-feature self-interaction norm |V_d[f_d]|² — subtracted once so
        # the closed form equals the host's strict p<q pair sum
        sn = np.einsum("dk,dk->d", V[np.arange(D), field_idx], V[np.arange(D), field_idx])
        act = self._act()

        if self.precision == "bf16":
            w16 = jnp.asarray(w, jnp.bfloat16)
            M16 = jnp.asarray(M, jnp.bfloat16)
            V16 = jnp.asarray(V, jnp.bfloat16)
            sn16 = jnp.asarray(sn, jnp.bfloat16)

            def kernel(X):
                X16 = X.astype(jnp.bfloat16)
                f32 = jnp.float32
                wx = jnp.matmul(X16, w16, preferred_element_type=f32)
                T = jnp.einsum(
                    "da,dfk,bd->bafk", M16, V16, X16,
                    preferred_element_type=f32,
                )
                Q = jnp.einsum("bafk,bfak->b", T, T)
                diag = jnp.matmul(
                    X16 * X16, sn16, preferred_element_type=f32
                )
                s = (wx + 0.5 * (Q - diag)).astype(X.dtype)
                return s, act(s)
        else:

            def kernel(X):
                wx = X @ w
                T = jnp.einsum("da,dfk,bd->bafk", M, V, X)
                Q = jnp.einsum("bafk,bfak->b", T, T)
                diag = (X * X) @ sn
                s = wx + 0.5 * (Q - diag)
                return s, act(s)

        self._kernel = kernel

    def _lower_gbdt(self) -> None:
        import jax.numpy as jnp
        from jax import lax

        pred = self.predictor
        model = pred.model
        K = pred.K
        T = pred.use_rounds * K
        trees = model.trees[:T]
        # leaf-only trees contribute no names; the vocab may be empty
        names = sorted(
            {nm for t in trees for i, nm in enumerate(t.feat_name) if not t.is_leaf(i)}
        )
        self.vocab = {n: i for i, n in enumerate(names)}
        self._bias_col = None
        self._fill = math.nan  # absent feature routes to the default child

        def _prep(fmap: Dict[str, float]):
            return fmap.items()

        self._prep = _prep
        self._prep_is_identity = True

        N = max((t.n_nodes() for t in trees), default=1)
        feat = np.full((max(T, 1), N), -1, np.int32)
        split = np.zeros((max(T, 1), N), np.float64)
        left = np.zeros((max(T, 1), N), np.int32)
        right = np.zeros((max(T, 1), N), np.int32)
        dleft = np.ones((max(T, 1), N), np.int32)
        leaf = np.zeros((max(T, 1), N), np.float64)
        for ti, t in enumerate(trees):
            n = t.n_nodes()
            for nid in range(n):
                if not t.is_leaf(nid):
                    feat[ti, nid] = self.vocab[t.feat_name[nid]]
            split[ti, :n] = t.split
            left[ti, :n] = t.left
            right[ti, :n] = t.right
            dleft[ti, :n] = np.asarray(t.default_left, np.int32)
            leaf[ti, :n] = t.leaf_value
        depth = max((t.max_depth() for t in trees), default=0)
        is_rf = pred.learn_type == "random_forest"
        rounds = max(pred.use_rounds, 1)
        base = float(model.base_prediction)
        act = self._act()
        # device-resident constants: fori_loop indexes them with a traced t
        feat, split, left, right, dleft, leaf = (
            jnp.asarray(a) for a in (feat, split, left, right, dleft, leaf)
        )

        def kernel(X):
            B = X.shape[0]
            rowsB = jnp.arange(B)[:, None]  # (B, 1)
            tids = jnp.arange(max(T, 1))[None, :]  # (1, T)
            # walk EVERY tree at once: `depth` steps over (B, T) frontiers
            # instead of T sequential per-tree loops — the tiny-op tail was
            # the serve kernel's bottleneck on CPU
            node = jnp.zeros((B, max(T, 1)), jnp.int32)
            for _ in range(depth):
                f = feat[tids, node]
                v = X[rowsB, jnp.maximum(f, 0)]
                go_left = jnp.where(
                    jnp.isnan(v), dleft[tids, node] > 0, v <= split[tids, node]
                )
                nxt = jnp.where(go_left, left[tids, node], right[tids, node])
                node = jnp.where(f < 0, node, nxt)
            contrib = leaf[tids, node]  # (B, T)

            # tree-ascending sequential accumulation in f64: bit-identical
            # to the host predictor's walk (serve_bench pins this); a
            # jnp.sum would reassociate the adds and drift in the last ulp
            if K == 1:
                s = lax.fori_loop(
                    0, T, lambda t, s: s + contrib[:, t],
                    jnp.zeros(B, jnp.float64),
                )
            else:
                s = lax.fori_loop(
                    0, T, lambda t, s: s.at[:, t % K].add(contrib[:, t]),
                    jnp.zeros((B, K), jnp.float64),
                )
            if is_rf:
                s = s / rounds
            s = s + base
            return s, act(s)

        self._kernel = kernel

        # -- rung lowering (fused / binned) -------------------------------
        # the bit-identity stacked kernel above stays built either way:
        # it is the downgrade target when a rung cannot lower
        if self.requested_mode == "stacked":
            return
        if K != 1:
            self._downgrade(
                f"{self.requested_mode}_to_stacked",
                "multiclass ensemble (K > 1)",
            )
            return
        if self.requested_mode == "fused":
            self._try_fused_gbdt(trees, is_rf, rounds, base, act)
        else:
            self._try_binned_gbdt(trees, is_rf, rounds, base, act)

    def _downgrade(self, kind: str, reason: str) -> None:
        """Named rung fallback: counter + flight-ring event + log — a
        Mosaic/toolchain failure must be visible, never silent."""
        obs_inc("serve.downgrade.total")
        obs_inc(f"serve.downgrade.{kind}")
        obs_event("serve.downgrade", kind=kind, reason=reason[:200])
        log.warning("serve rung downgrade %s: %s", kind, reason)

    def _try_fused_gbdt(self, trees, is_rf, rounds, base, act) -> None:
        import jax.numpy as jnp

        from . import kernels

        heap, why = kernels.build_heap(trees, self.vocab)
        if heap is None:
            self._downgrade("fused_to_stacked", why)
            return
        feat_j = jnp.asarray(heap.feat)
        split_j = jnp.asarray(heap.split)
        dl_j = jnp.asarray(heap.dleft)
        leaf_j = jnp.asarray(heap.leaf)
        depth = heap.depth
        interp = self._fused_interpret
        # AOT probe: ONE eager run at the LARGEST rung — the row wave is
        # VMEM-resident, so the widest shape is the binding compile; a
        # Mosaic/VMEM failure (or a CPU backend, where the kernel cannot
        # compile at all) downgrades here at load time, never mid-request
        try:
            with compile_credit():
                dummy = jnp.asarray(
                    np.full((len(self.vocab), self.ladder[-1]), math.nan)
                )
                kernels.fused_scores(
                    dummy, feat_j, split_j, dl_j, leaf_j, depth,
                    interpret=interp,
                )
        except Exception as e:  # noqa: BLE001 — any lowering failure downgrades
            self._downgrade(
                "fused_to_stacked", f"{type(e).__name__}: {e}"
            )
            return

        def kernel(X):
            s = kernels.fused_scores(
                jnp.transpose(X), feat_j, split_j, dl_j, leaf_j, depth,
                interpret=interp,
            )
            if is_rf:
                s = s / rounds
            s = s + base
            return s, act(s)

        self._kernel = kernel
        self.mode = "fused"
        self.backend = "fused-pallas-interpret" if interp else "fused-pallas"

    def _try_binned_gbdt(self, trees, is_rf, rounds, base, act) -> None:
        import jax
        import jax.numpy as jnp

        from ..gbdt.binning import bin_edges_path, load_bin_edges
        from . import kernels

        heap, why = kernels.build_heap(trees, self.vocab)
        if heap is None:
            self._downgrade("binned_to_stacked", why)
            return
        edges = None
        data_path = getattr(self.predictor.params.model, "data_path", None)
        if data_path:
            from ..gbdt.binning import model_text_digest

            try:
                with self.predictor.fs.open(data_path) as f:
                    digest = model_text_digest(f.read())
            except OSError:
                digest = None  # sidecar range checks still apply below
            edges = load_bin_edges(
                self.predictor.fs, bin_edges_path(data_path),
                model_digest=digest,
            )
        table, why = kernels.build_bin_table(trees, self.vocab, edges)
        if table is None:
            self._downgrade("binned_to_stacked", why)
            return
        packed = kernels.pack_heap_nodes(heap, table)
        depth, sentinel = heap.depth, table.sentinel
        interp = self._fused_interpret
        on_tpu = jax.default_backend() == "tpu"
        backend = None

        def tail(s):
            if is_rf:
                s = s / rounds
            s = s + base
            return s, act(s)

        if on_tpu or interp:
            # Pallas binned front: same probe discipline as the fused rung
            feat_j = jnp.asarray(heap.feat)
            rank1_j = jnp.asarray(
                (packed >> kernels.FEAT_BITS)
                & ((1 << kernels.RANK_BITS) - 1)
            )
            dl_j = jnp.asarray(heap.dleft)
            leaf_j = jnp.asarray(heap.leaf)
            try:
                with compile_credit():
                    dummy = jnp.full(
                        (len(self.vocab), self.ladder[-1]), sentinel,
                        jnp.int32,
                    )
                    kernels.binned_scores_pallas(
                        dummy, feat_j, rank1_j, dl_j, leaf_j, depth,
                        sentinel, interpret=interp,
                    )

                def binned_kernel(bw):
                    s = kernels.binned_scores_pallas(
                        jnp.transpose(bw), feat_j, rank1_j, dl_j, leaf_j,
                        depth, sentinel, interpret=interp,
                    )
                    return tail(s)

                backend = (
                    "binned-pallas-interpret" if interp else "binned-pallas"
                )
            except Exception as e:  # noqa: BLE001 — fall through the binned chain
                # still the binned rung, but on the slower XLA walk — a
                # Mosaic regression must trip dashboards like every other
                # rung fallback, not hide as a quiet throughput drop
                self._downgrade(
                    "binned_pallas_to_xla", f"{type(e).__name__}: {e}"
                )
        np_act = numpy_activation(self.predictor.loss)
        if backend is None and not on_tpu:
            native_ok = (
                np_act is not None and kernels.native_serve_available()
            )
            if not native_ok and not knobs.get_bool("YTK_NO_NATIVE"):
                self._downgrade(
                    "binned_native_to_xla",
                    "native serve kernel unavailable (toolchain?)"
                    if np_act is not None
                    else "no numpy activation for this loss",
                )
        else:
            native_ok = False
        if backend is None and native_ok:
            threads = kernels.resolve_kernel_threads()
            heap_leaf = np.ascontiguousarray(heap.leaf)

            def exec_native(chunk):
                bins = kernels.bin_rows(chunk, table)
                s = kernels.native_binned_scores(
                    bins, packed, heap_leaf, depth, sentinel, threads,
                )
                if is_rf:
                    s = s / rounds
                s = s + base
                return s, np_act(s)

            self._exec = exec_native
            backend = "binned-native"
        if backend is None:
            run = kernels.make_binned_xla(packed, heap.leaf, depth, sentinel)

            def binned_kernel(bw):  # noqa: F811 — the chain picks exactly one
                return tail(run(bw))

            backend = "binned-xla"
        if backend != "binned-native":
            binned_jit = jax.jit(binned_kernel)

            def exec_binned(chunk):
                bins = kernels.bin_rows(chunk, table).astype(np.int32)
                return jax.device_get(binned_jit(jnp.asarray(bins)))

            self._exec = exec_binned
        self.mode = "binned"
        self.backend = backend
        self.bin_mode = table.mode
        self.bin_dtype = str(np.dtype(table.dtype))
        self._bin_table = table  # introspection / tests

    def _lower_gbst(self) -> None:
        import jax.numpy as jnp
        from jax import lax

        pred = self.predictor
        K = pred.K
        T = pred.n_trees
        stride = pred.stride
        bias_name = pred.params.model.bias_feature_name
        names = sorted({n for tmap in pred.tree_maps for n in tmap})
        has_bias = pred.params.model.need_bias
        if has_bias:
            names = [n for n in names if n != bias_name]
        self.vocab = {n: i for i, n in enumerate(sorted(names))}
        self._bias_col = len(self.vocab) if has_bias else None
        self._prep = pred._prep  # bias handled via the dedicated column
        D = len(self.vocab) + (1 if has_bias else 0)
        W = np.zeros((max(T, 1), D, stride), np.float64)
        for ti, tmap in enumerate(pred.tree_maps):
            for n, row in tmap.items():
                if has_bias and n == bias_name:
                    W[ti, self._bias_col] = row
                elif n in self.vocab:
                    W[ti, self.vocab[n]] = row
        leaves = np.stack(pred.leaves) if pred.leaves else np.zeros((1, K))
        W = jnp.asarray(W)  # fori_loop indexes with a traced t
        leaves = jnp.asarray(leaves)
        hier = pred.hier
        scalar = pred.scalar_leaves
        lr = pred.lr
        is_rf = pred.is_rf
        base = pred.base_score
        levels = int(math.log2(K)) if K > 1 else 0
        act = self._act()

        def gate(gate_in):
            B = gate_in.shape[0]
            if hier:
                sig = 1.0 / (1.0 + jnp.exp(-gate_in))
                level = jnp.ones((B, 1), gate_in.dtype)
                for _ in range(levels):
                    n = level.shape[1]
                    gates = sig[:, n - 1 : 2 * n - 1]
                    level = jnp.stack(
                        [level * gates, level * (1.0 - gates)], axis=-1
                    ).reshape(B, 2 * n)
                return level
            z = jnp.concatenate([gate_in, jnp.zeros((B, 1), gate_in.dtype)], -1)
            z = z - jnp.max(z, axis=-1, keepdims=True)
            e = jnp.exp(z)
            return e / jnp.sum(e, axis=-1, keepdims=True)

        def kernel(X):
            B = X.shape[0]

            def per_tree(t, z):
                if scalar:
                    gate_in = X @ W[t]
                    experts = leaves[t][None, :]
                else:
                    gate_in = X @ W[t][:, : K - 1]
                    experts = X @ W[t][:, K - 1 :]
                pi = gate(gate_in)
                fx = jnp.sum(pi * experts, axis=-1)
                return z + lr * fx

            z = jnp.full((B,), base, jnp.float64)
            z = lax.fori_loop(0, T, per_tree, z) if T else z
            if is_rf:
                z = z / max(T, 1)
            return z, act(z)

        self._kernel = kernel
