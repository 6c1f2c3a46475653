"""GBDT boosting trainer — histogram trees on the TPU.

Rebuild of reference optimizer/GBDTOptimizer.java (boosting driver,
:174-530) + optimizer/gbdt/DataParallelTreeMaker.java:229-653 (histogram
build, split enumeration, position update) + UpdateStrategy.java:64-83
(gain / leaf-value formulas incl. L1 soft-threshold + leaf clamp) +
TreeRefiner.java (LAD weighted-median leaves).

Two growth engines share the split/gain kernels (gbdt/engine.py):

  device (default, this file) — the whole tree grows inside one XLA
    program (engine.make_grow_tree): Pallas one-hot-matmul histograms,
    on-device frontier selection, sibling subtraction in a device
    histogram pool, and per-round score/loss updates — zero host
    round-trips per round.
  host (gbdt/host_engine.py) — the original per-level/per-split host
    loop. Kept as the reference implementation for equivalence tests, and
    used automatically for precise LAD leaf refinement and the
    feature-parallel maker. It calls what both engines share here.

TPU-first design notes:
  - the bin matrix lives transposed (F, n) so routing is a row
    dynamic-slice + lane compare, and the Pallas kernel reads lane-major
  - histograms are one fused MXU pass per wave; with rows sharded over a
    mesh XLA psums the partial histograms (the reduceScatterArray of
    HistogramBuilder.java:95 without hand-rolling)
  - split enumeration is a cumulative-sum scan over all (node, feature,
    bin) at once; first-max argmax reproduces SplitInfo.needReplace's
    lower-slot tie-break
"""

from __future__ import annotations

import contextlib
import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import knobs
from ..config.params import GBDTParams
from ..eval import EvalSet
from ..io.fs import FileSystem, LocalFileSystem
from ..losses import create_loss
from ..obs import (
    enabled as obs_enabled,
    event as obs_event,
    gauge as obs_gauge,
    health,
    inc as obs_inc,
    profiler,
    recorder,
    root_span as obs_root_span,
    scopes as obs_scopes,
    span as obs_span,
    step_span as obs_step_span,
)
from ..resilience import chaos_point, trainer_guard
from .binning import (
    ColumnsT,
    FeatureBins,
    bin_matrix,
    bin_matrix_device,
    build_bins_global,
    build_bins_maybe_device,
    build_bundle_plan,
    bundle_bin_matrix_t,
    feature_chunk,
)
from .data import GBDTData, GBDTIngest, column_stats
from .engine import (
    GrowSpec,
    make_gain_fns,
    make_grow_tree,
    wave_log_rows,
)
from .hist import BM_DEFAULT, fused_holds, pad_inputs
from .host_engine import train_host
from .route import leaf_values
from .tree import (
    GBDTModel,
    Tree,
    _traverse_kernel,
    _wavg_loss,
    unbundle_tree,
)

log = logging.getLogger("ytklearn_tpu.gbdt")


# ---------------------------------------------------------------------------
# The trainer
# ---------------------------------------------------------------------------


# Which partitioned histogram passes the device engine builds: each
# implementation family's budget divisors and the largest budget the fused
# kernel takes (GrowSpec.rungs makes them the passes at a row count).
# Chosen in code from the platform, not by the environment. On since r6
# everywhere. The TPU ladder routes only genuinely late waves (<= n/64
# rows) into partitioned passes, all through the fused kernel — the
# XLA-gather rungs at n/8, n/32 measured as net losers on TPU in r5; the
# dense family keeps the r5 ladder (gathers are cheap on CPU). All three
# values date from r5/r6, a retired set-up (an experimental TPU plug-in,
# on older code), not re-measured: in gbdt_higgs.train on the v5e neither
# TPU rung ever runs (ledger `breakdown`). ROADMAP S2 re-measures them.
LADDER = {"pallas": (64, 256), "dense": (8, 32)}
FUSED_MAX_ROWS = 1 << 18
# The TPU ladder where the fused kernel does not hold the width
# (hist.fused_holds: its whole (F, 3N, B) output lives in VMEM): XLA row
# gathers feeding the full-scan kernel, no fused rung. A gathered pass
# costs its rows x F whatever the budget's share of n, so the rungs sit
# where a pass is dearest: n/4 and n/16 (114,688 and 32,768 rows at 409,600
# x 2,000, where over 20 trees they ran 78 and 2 of 223 passes: PERF.md
# section 5, my chip runs, PR 39). Not tuned beyond that: ROADMAP A7.
WIDE_LADDER = (4, 16)
# The largest tree (GrowSpec.max_nodes) whose end-of-tree leaf lookup the
# one-pass kernel takes (GrowSpec.leaf_lookup; route.leaf_values): it costs
# a lane gather and a select per 128 nodes a row, XLA's gather 8.2 ns an
# index whatever the table holds. At 10.5M rows on the v5e: 0.35 ms at 509
# nodes, 0.79 at 2,045, 2.9 at 8,189 against the gather's 90-102 ms (my
# chip runs, PR 34); the kernel unrolls a step per 128 nodes and nothing
# larger was compiled or timed, so a larger tree keeps the gather.
LEAF_KERNEL_MAX_NODES = 1 << 13


@contextlib.contextmanager
def _sync_span(step: int, **args):
    """A `gbdt.sync` span: it lasts as long as the host stands waiting for
    the device, and `gbdt.sync_wait_s` is the sum of their durations."""
    with obs_span("gbdt.sync", step=step, **args) as sp:
        yield sp
    obs_inc("gbdt.sync_wait_s", sp.dur)


@jax.jit
def sync_slice(buf, rnd):
    """One round's entry of a per-round loss buffer: the small program a
    sync enqueues, under a name of its own (`jit_sync_slice` on the
    trace's module line)."""
    return buf[rnd]


@dataclass
class _DevInputs:
    """Device-resident training inputs prepared once per run (the device
    engine's CoreData equivalent): transposed/padded bin matrices, labels,
    weights, and the program shapes they were padded for."""

    bins: FeatureBins
    bins_t: jnp.ndarray  # (F_prog, n_pad) transposed bin matrix
    y: jnp.ndarray
    weight: jnp.ndarray
    real_mask: jnp.ndarray
    n_score: int  # global (cross-process) padded row count
    F: int  # engine-visible real column count (EFB-bundled when active)
    F_prog: int  # feature axis padded to the mesh device count
    B: int  # bin axis padded to a power of two
    D: int  # mesh device count
    aux_bins: tuple  # () or (bins_t of the test set,)
    y_t: Optional[jnp.ndarray]
    w_t: Optional[jnp.ndarray]
    nt_score: int

    def device_arrays(self) -> tuple:
        """What the preprocess span settles on."""
        return (self.bins_t, self.y, self.weight, self.real_mask,
                self.aux_bins, self.y_t, self.w_t)


@dataclass
class GBDTResult:
    model: GBDTModel
    train_loss: float
    test_loss: Optional[float]
    train_metrics: Dict[str, float] = field(default_factory=dict)
    test_metrics: Dict[str, float] = field(default_factory=dict)
    round_log: List[Dict] = field(default_factory=list)


class GBDTTrainer:
    # "contract:" marks what perfbench/families/gbdt.py depends on: name,
    # signature and place on the instance stay
    # (tests/test_gbdt_engine.py::test_benchmark_contract). Here: params,
    # mesh, fs and, for the int8 control, hist_precision
    def __init__(
        self,
        params: GBDTParams,
        mesh=None,
        fs: Optional[FileSystem] = None,
        engine: str = "auto",
        wave: Optional[int] = None,
        hist_precision: str = "bf16",  # bf16 | f32 | int8
        goss: Optional[Tuple[float, float]] = None,  # (a, b); a >= 1 = off
        efb: Optional[bool] = None,  # None = YTK_EFB knob
    ):
        self.params = params
        self.mesh = mesh
        self.fs = fs or LocalFileSystem()
        self.loss = create_loss(
            params.loss_function, {"sigmoid_zmax": params.sigmoid_zmax}
        )
        cfg = self._cfg()
        self.gain_fn, self.node_value_fn = make_gain_fns(*cfg)
        self.K = params.num_tree_in_group
        if engine == "auto":
            # precise LAD leaf refinement (lad_refine_appr=false) is a
            # host-side sort, so it rides the host engine; the approximate
            # default runs inside the device engine's jitted round. The
            # feature-parallel maker is a host-loop maker by design.
            engine = (
                "host"
                if (params.loss_function == "l1" and self.K == 1
                    and not params.lad_refine_appr)
                or params.tree_maker == "feature"
                else "device"
            )
        self.engine = engine
        self.wave = wave
        if hist_precision not in ("bf16", "f32", "int8"):
            raise ValueError(
                f"hist_precision must be bf16|f32|int8, got {hist_precision!r}"
            )
        self.hist_precision = hist_precision
        # GOSS (device engine): explicit ctor pair wins, else the knobs.
        # a >= 1 disables — the engine then takes the bit-identical
        # unsampled path.
        if goss is None:
            goss = (
                knobs.get_float("YTK_GOSS_A"),
                knobs.get_float("YTK_GOSS_B"),
            )
        a, b = float(goss[0]), float(goss[1])
        if not (0.0 < a <= 1.0) or not (0.0 <= b <= 1.0):
            raise ValueError(
                f"goss=(a, b) needs 0 < a <= 1 and 0 <= b <= 1, got {goss!r}"
            )
        self.goss = (a, b)
        if a < 1.0 and self.engine == "host":
            log.warning(
                "GOSS (goss_a=%.3f) is a device-engine feature; the host "
                "engine trains unsampled", a,
            )
        self.efb = knobs.get_bool("YTK_EFB") if efb is None else bool(efb)
        if self.efb and self.engine == "host":
            # warn only on an explicit request — the knob defaults to on,
            # so every host-engine run would otherwise nag
            (log.warning if efb else log.info)(
                "EFB is a device-engine feature; the host engine trains "
                "on the unbundled bin matrix"
            )

    def _put(self, arr):
        """Row-shard dim 0. Multi-process: `arr` is this process's shard."""
        if self.mesh is None:
            return jax.device_put(arr)
        from ..parallel.mesh import put_row_sharded

        return put_row_sharded(arr, self.mesh)

    def _put_cols(self, arr):
        """Shard the trailing (sample) axis of a transposed matrix;
        multi-process: `arr` carries this process's sample columns."""
        if self.mesh is None:
            return jax.device_put(arr)
        from ..parallel.mesh import put_col_sharded

        return put_col_sharded(arr, self.mesh)

    def _cfg(self):
        p = self.params
        return (p.l1, p.l2, p.min_child_hessian_sum, p.max_abs_leaf_val)

    def _load_resume_model(self, model: GBDTModel, K: int, feature_names=None):
        """continue_train reload (reference: GBDTOptimizer.java:408 resume at
        trees/K). Rank0 reads, every rank resumes from rank0's text — dumps
        are rank0-only, so on non-shared storage other ranks would
        otherwise silently start from scratch and corrupt the run.

        Tree.parse leaves `feat` at 0 for non-numeric feature names
        ("resolved later via feature dict"); the resolution happens HERE
        against the ingest column order — without it every resumed score
        replay routed through column 0, so warm starts trained against a
        corrupted residual (found by the preemption bit-identity pin,
        tests/test_resilience.py)."""
        p = self.params
        if not p.model.continue_train:
            return model, 0
        from ..parallel.collectives import load_on_rank0

        def read():
            if not self.fs.exists(p.model.data_path):
                return None
            with self.fs.open(p.model.data_path) as f:
                return f.read()

        text = load_on_rank0(read)
        if text is None:
            return model, 0
        model = GBDTModel.loads(text)
        if feature_names:
            index = {n: i for i, n in enumerate(feature_names)}
            for t in model.trees:
                for nid in range(t.n_nodes()):
                    if t.is_leaf(nid):
                        continue
                    fid = index.get(t.feat_name[nid])
                    if fid is not None:
                        t.feat[nid] = fid
                    elif not t.feat_name[nid].isdigit():
                        raise ValueError(
                            f"continue_train: dumped split feature "
                            f"{t.feat_name[nid]!r} is not in this run's "
                            "feature set — resuming on different data?"
                        )
        log.info("continue_train: loaded %d trees", len(model.trees))
        return model, len(model.trees) // K

    def _shard_target(self, bins_np) -> Optional[int]:
        """Mesh>1: pad rows so the sample axis splits evenly across all mesh
        devices AND each device shard is Pallas-tileable (bm-divisible on
        TPU; a small multiple suffices for the dense CPU path). Multi-
        process: cross-process equalized target. Single device: None =
        pad_inputs' default bm rounding."""
        if self.mesh is not None and (
            jax.process_count() > 1 or self.mesh.devices.size > 1
        ):
            from ..parallel.mesh import equal_row_target

            mult = BM_DEFAULT if jax.default_backend() == "tpu" else 8
            return equal_row_target(bins_np.shape[0], self.mesh, multiple=mult)
        return None

    # -- entry ------------------------------------------------------------

    # contract: the benchmark calls train(train=, test=) with rows it made
    def train(
        self,
        train: Optional[GBDTData] = None,
        test: Optional[GBDTData] = None,
    ) -> GBDTResult:
        # preemption-safe: SIGTERM/SIGINT defer to the next round
        # boundary, where the loop dumps an emergency checkpoint through
        # the ordinary atomic dump path and raises Preempted — `--resume
        # auto` re-enters here via continue_train (docs/fault_tolerance.md)
        # `train.run`: the root of every span of the run (the benchmark
        # enters here; the CLI has opened it around the data load already)
        with obs_root_span("train.run", family="gbdt"), trainer_guard(self):
            if self.engine == "device":
                return self._train_device(train, test)
            if jax.process_count() > 1:
                raise ValueError(
                    "multi-process GBDT training requires the device engine "
                    "(host-loop makers read per-row device state eagerly); "
                    f"got engine={self.engine!r}"
                )
            return train_host(self, train, test)

    # ======================================================================
    # DEVICE ENGINE
    # ======================================================================

    def _grow_spec(self, F: int, B: int, goss_scale: float = 1.0) -> GrowSpec:
        p = self.params
        caps = []
        if p.max_leaf_cnt > 0:
            caps.append(2 * p.max_leaf_cnt - 1)
        if p.max_depth > 0:
            caps.append(2 ** (p.max_depth + 1) - 1)
        if not caps:
            raise ValueError("gbdt needs optimization.max_depth or max_leaf_cnt")
        M = min(caps)
        if self.wave is not None:
            NW = self.wave
        else:
            # 64 beat 32 and 128 at Higgs scale with quality inside the
            # band (r5, a retired set-up; not re-measured): a wave's MACs
            # are 3N*B a row and feature, its bin one-hot B compares
            # whatever N (from 32 nodes on at B = 256; a narrower wave's is
            # factored: hist.onehot_split), so wider waves raise MXU
            # utilization; 128 over-relaxes best-first and pays for unused
            # frontier slots
            NW = 64
        NW = max(1, min(NW, (M + 1) // 2))
        # the implementation family, resolved here and nowhere below: the
        # Pallas kernels on the chip, their dense einsum / XLA twins only
        # where Mosaic can't compile (CPU tests / virtual mesh); mesh>1
        # runs the SAME Pallas kernels per shard under shard_map
        kernels = "pallas" if jax.default_backend() == "tpu" else "dense"
        # what the width decides, here and in GrowSpec.route / .packed and
        # nowhere else: whether any rung is fused
        ladder, fused_max_rows = LADDER[kernels], FUSED_MAX_ROWS
        if kernels == "pallas" and not fused_holds(F, NW, B):
            ladder, fused_max_rows = WIDE_LADDER, 0
        return GrowSpec(
            F=F,
            B=B,
            max_nodes=M,
            wave=NW,
            policy=p.tree_grow_policy,
            max_depth=p.max_depth,
            max_leaves=p.max_leaf_cnt,
            lr=p.learning_rate,
            l1=p.l1,
            l2=p.l2,
            min_h=p.min_child_hessian_sum,
            max_abs=p.max_abs_leaf_val,
            min_split_loss=p.min_split_loss,
            min_split_samples=float(p.min_split_samples),
            precision=self.hist_precision,
            kernels=kernels,
            ladder=ladder,
            fused_max_rows=fused_max_rows,
            goss_a=self.goss[0],
            goss_b=self.goss[1],
            goss_scale=goss_scale,
        )

    def _prep_device_inputs(self, train: GBDTData, test: Optional[GBDTData]):
        """Binning + padding + device placement for the device engine.

        Returns a _DevInputs with the transposed/padded bin matrices (and
        test-set twins), label/weight/real-row arrays, and the padded
        feature count F_prog the growth program is shaped for."""
        p = self.params
        n_real, F = train.n_real, train.n_features
        self._missing_fill = train.missing_fill

        log.info("building bins (%d features)...", F)
        # single-device: bin on the TPU (sort + rank-pick + compare-count);
        # the host path costs ~4s/feature at 10M rows (reference load+
        # preprocess budget: 35s, docs/gbdt_experiments.md)
        use_dev_bin = (
            self.mesh is None or self.mesh.devices.size == 1
        ) and jax.process_count() == 1
        if use_dev_bin:
            # (F, n) real rows: one transposed copy, or past binning's byte
            # budget a range of columns at a time and no whole copy
            cols = ColumnsT(train.X)
            bins = build_bins_maybe_device(
                train.X, cols, train.weight, p, train.feature_names
            )
        else:
            cols = None
            bins = build_bins_global(train.X, train.weight, p, train.feature_names)
        B_real = bins.max_bins
        B = max(8, 1 << (B_real - 1).bit_length())  # pad to pow2 for tiling
        # EFB: merge mutually-exclusive sparse columns into offset-binned
        # bundles BEFORE the matrix reaches HBM. Bundles are capped at the
        # padded bin width B, so the histogram shape never grows; the
        # engine's range tables + tree unbundling keep splits (and every
        # dumped model) in original feature space. Warm starts
        # (continue_train) stay bundled: the incumbent's score replay runs
        # on a transient PRE-bundle matrix (original feature space), so
        # re-bundling is exact — see _init_device_scores. Only the
        # multi-process case downgrades (the plan would need a cross-
        # process conflict merge), and it does so loudly: an operator who
        # asked for EFB must see the fallback in logs AND obs.
        plan = None
        if self.efb and jax.process_count() > 1:
            log.warning(
                "EFB disabled: multi-process runs would need a cross-"
                "process conflict merge; training unbundled"
            )
            obs_inc("gbdt.efb.downgrade")
            obs_event("gbdt.efb.downgrade", reason="multi_process")
        elif self.efb:
            budget = knobs.get_int("YTK_EFB_CONFLICT")
            with obs_span("gbdt.efb.plan", F=F):
                if use_dev_bin:
                    plan = build_bundle_plan(cols, bins, budget, B)
                else:
                    nnz, mins = column_stats(train.X)
                    plan = build_bundle_plan(
                        train.X.T, bins, budget, B, nnz=nnz, mins=mins
                    )
            if plan is not None:
                log.info("EFB: %s (conflict budget %d)", plan.summary(), budget)
                obs_inc("gbdt.efb.bundles", len(plan.bundles))
                obs_inc("gbdt.efb.features_bundled", plan.n_bundled_features)
                obs_gauge("gbdt.stat.efb_cols_saved", float(F - plan.n_cols))
        self._efb_plan = plan
        # serve-side binned scoring reads these back from the dumped
        # sidecar (`<data_path>.bins.json`); edges are per ORIGINAL
        # feature, pre-EFB, like the dumped trees
        self._bins_sidecar = (list(train.feature_names or []), bins)
        self._quality_features = self._build_quality_features(train)
        F_cols = plan.n_cols if plan is not None else F
        # mesh>1: the growth program runs under shard_map with each device
        # owning a contiguous feature slice of the histograms — pad the
        # feature axis so it divides evenly (padded features: all rows in
        # bin 0 + masked off, so they can never split)
        D = 1 if self.mesh is None else int(self.mesh.devices.size)
        F_prog = -(-F_cols // D) * D
        # warm-start + EFB: the incumbent's trees split on ORIGINAL feature
        # ids, so the score replay needs the pre-bundle matrix; keep it as
        # a transient (n_pad, F) row matrix that _init_device_scores frees
        # right after the replay
        keep_replay = plan is not None and p.model.continue_train
        self._replay_bins = None
        # one-byte bins quarter the routing and DMA traffic
        small = jnp.uint8 if B <= 256 else jnp.int32
        if use_dev_bin:
            n_rows = train.X.shape[0]
            n_pad = -(-n_rows // BM_DEFAULT) * BM_DEFAULT
            bins_t_raw = bin_matrix_device(cols, bins, n_pad=n_pad, dtype=small)
            bins_t = (
                bundle_bin_matrix_t(bins_t_raw, plan)
                if plan is not None
                else bins_t_raw
            )
            if keep_replay:
                self._replay_bins = [jnp.transpose(bins_t_raw)]
            del cols, bins_t_raw
        else:
            bins_np_raw = bin_matrix(train.X, bins)
            if plan is not None:
                bins_np = np.asarray(
                    bundle_bin_matrix_t(bins_np_raw.T, plan)
                ).T
            else:
                bins_np = bins_np_raw
            bins_t_np, n_pad = pad_inputs(
                bins_np, n_pad=self._shard_target(bins_np), F_pad=F_prog
            )
            bins_t = self._put_cols(bins_t_np)
            if keep_replay:
                self._replay_bins = [
                    self._put(
                        _pad0(bins_np_raw.astype(np.int32), n_pad)
                    )
                ]
            del bins_np_raw
        y = self._put(_pad0(train.y, n_pad))
        weight = self._put(_pad0(train.weight, n_pad))
        real_mask = self._put(np.arange(n_pad) < train.X.shape[0])
        # global row count (the score/tree program shapes); n_pad stays the
        # per-process shard length
        n_score = n_pad * jax.process_count()

        aux_bins = ()
        y_t = w_t = None
        nt_score = 0
        if test is not None:
            if use_dev_bin:
                nt = test.X.shape[0]
                nt_pad = -(-nt // BM_DEFAULT) * BM_DEFAULT
                bt_raw = bin_matrix_device(
                    ColumnsT(test.X), bins, n_pad=nt_pad, dtype=small
                )
                bt_dev = (
                    bundle_bin_matrix_t(bt_raw, plan)
                    if plan is not None
                    else bt_raw
                )
                aux_bins = (bt_dev,)
                if keep_replay:
                    self._replay_bins.append(jnp.transpose(bt_raw))
                del bt_dev, bt_raw
            else:
                bins_test_raw = bin_matrix(test.X, bins)
                if plan is not None:
                    bins_test_np = np.asarray(
                        bundle_bin_matrix_t(bins_test_raw.T, plan)
                    ).T
                else:
                    bins_test_np = bins_test_raw
                bt_np, nt_pad = pad_inputs(
                    bins_test_np, n_pad=self._shard_target(bins_test_np),
                    F_pad=F_prog,
                )
                aux_bins = (self._put_cols(bt_np),)
                if keep_replay:
                    self._replay_bins.append(
                        self._put(
                            _pad0(bins_test_raw.astype(np.int32), nt_pad)
                        )
                    )
                del bins_test_raw
            y_t = self._put(_pad0(test.y, nt_pad))
            w_t = self._put(_pad0(test.weight, nt_pad))
            nt_score = nt_pad * jax.process_count()
        log.info(
            "%d rows, %d features, %d bins (pad %d)", n_real, F, B_real, B
        )
        return _DevInputs(
            bins=bins, bins_t=bins_t, y=y, weight=weight, real_mask=real_mask,
            n_score=n_score, F=F_cols, F_prog=F_prog, B=B, D=D,
            aux_bins=aux_bins, y_t=y_t, w_t=w_t, nt_score=nt_score,
        )

    def _init_device_scores(self, model: GBDTModel, dd: "_DevInputs", base_np):
        """Base-score init + continue_train score replay through host trees."""
        K = self.K
        if K > 1:
            scores = jnp.full((dd.n_score, K), base_np, jnp.float32)
        else:
            scores = jnp.full((dd.n_score,), float(base_np), jnp.float32)
        scores_t = None
        if dd.y_t is not None:
            if K > 1:
                scores_t = jnp.full((dd.nt_score, K), base_np, jnp.float32)
            else:
                scores_t = jnp.full((dd.nt_score,), float(base_np), jnp.float32)
        if model.trees:
            # EFB warm start: the incumbent's trees split on original
            # feature ids, so replay walks the transient PRE-bundle matrix
            # (_prep_device_inputs keeps it only for this loop); bundled
            # training then proceeds on dd.bins_t as usual
            replay = getattr(self, "_replay_bins", None)
            if replay is not None:
                bins_dev = replay[0]
                bins_test_dev = replay[1] if len(replay) > 1 else None
            else:
                bins_dev = jnp.transpose(dd.bins_t)
                bins_test_dev = (
                    jnp.transpose(dd.aux_bins[0]) if dd.aux_bins else None
                )
            for i, t in enumerate(model.trees):
                add = self._tree_scores_from_raw(t, dd.bins, bins_dev)
                scores = scores.at[:, i % K].add(add) if K > 1 else scores + add
                if scores_t is not None:
                    add_t = self._tree_scores_from_raw(t, dd.bins, bins_test_dev)
                    scores_t = (
                        scores_t.at[:, i % K].add(add_t) if K > 1 else scores_t + add_t
                    )
            del bins_dev, bins_test_dev
        self._replay_bins = None  # free the pre-bundle replay matrices
        return scores, scores_t

    def _make_tree_bufs(self, M: int):
        """Whole-run tree buffers, written on device, fetched once."""
        p = self.params
        T = p.round_num * self.K
        bufs = {
            "feat": jnp.full((T, M), -1, jnp.int32),
            "slot": jnp.zeros((T, M), jnp.int32),
            "slot_r": jnp.zeros((T, M), jnp.int32),
            "left": jnp.full((T, M), -1, jnp.int32),
            "right": jnp.full((T, M), -1, jnp.int32),
            "leaf": jnp.zeros((T, M), jnp.float32),
            "gain": jnp.zeros((T, M), jnp.float32),
            "hess": jnp.zeros((T, M), jnp.float32),
            "cnt": jnp.zeros((T, M), jnp.float32),
            "n_nodes": jnp.zeros((T,), jnp.int32),
            # per-tree wave log from grow(): [rows_scanned, rows_needed,
            # splits, hist_width, rows_sampled] per histogram pass — the
            # roofline / O(wave rows) ablation record (~10 KB per tree)
            "wlog": jnp.zeros((T, wave_log_rows(M), 5), jnp.float32),
        }
        loss_buf = jnp.zeros((p.round_num,), jnp.float32)
        tloss_buf = jnp.zeros((p.round_num,), jnp.float32)
        return bufs, loss_buf, tloss_buf

    def _make_round_step(
        self, dd: "_DevInputs", grow, has_test: bool, spec: GrowSpec,
    ):
        """Build the jitted per-round program: grads -> K tree growths ->
        score/loss updates (reference: GBDTOptimizer.doBoost:482 +
        predictAndCalcLossGrad:513 as ONE device program per round)."""
        p = self.params
        K = self.K
        F, F_prog = dd.F, dd.F_prog
        # GOSS: grow() fits on the compacted sample and routes the full
        # train matrix as its first aux set — train leaf assignment comes
        # back in aux_pos[0], the caller-supplied aux sets shift by one
        goss_on = 0.0 < spec.goss_a < 1.0
        loss_fn = self.loss
        inst_rate = p.instance_sample_rate
        feat_rate = p.feature_sample_rate
        # LAD leaf refinement on device: the approximate quantile mode
        # (reference: TreeRefiner.java GK-sketch path, lad_refine_appr=true
        # default) as a rank-grid weighted median — exact when the grid
        # covers every row (n <= _LAD_Q)
        refine_lad = loss_fn.name == "l1" and K == 1
        if refine_lad and not p.lad_refine_appr:
            log.warning(
                "lad_refine_appr=false requests the precise sort-based "
                "refine, which only the host engine implements; the device "
                "engine uses the approximate rank-grid refine instead "
                "(pass engine='host' or leave engine='auto' for precise)"
            )
        # a row's leaf value at the end of a tree: the one-pass kernel or
        # XLA's gather, as the tree's size says; a shard's rows under a mesh
        leaf_of = partial(
            leaf_values,
            kernels=spec.leaf_lookup(LEAF_KERNEL_MAX_NODES),
            bm=spec.bm,
            mesh=self.mesh if dd.D > 1 else None,
            interpret=spec.fused_interpret,
        )

        def round_step(carry, rnd, key, data):
            bins_t, y, weight, real_mask = data[:4]
            aux_bins = (data[4],) if has_test else ()
            y_t, w_t = (data[5], data[6]) if has_test else (None, None)
            scores, scores_t, bufs, loss_buf, tloss_buf = carry
            preds = loss_fn.predict(scores)
            gs, hs = loss_fn.grad_hess(preds, y)
            kf, ki, kg = jax.random.split(key, 3)
            # weight-0 rows still count in the histogram count channel
            # (weight folds into g/h only), matching the host engine and the
            # reference's per-node sample counting
            include = real_mask
            if inst_rate < 1.0:
                include &= jax.random.uniform(ki, real_mask.shape) <= inst_rate
            if feat_rate < 1.0:
                fmask = jax.random.uniform(kf, (F,)) <= feat_rate
                fmask = fmask.at[0].set(fmask[0] | ~jnp.any(fmask))
            else:
                fmask = jnp.ones((F,), bool)
            if F_prog > F:  # padded features can never be sampled
                fmask = jnp.pad(fmask, (0, F_prog - F))

            for grp in range(K):
                g = (gs[:, grp] if K > 1 else gs) * weight
                h = (hs[:, grp] if K > 1 else hs) * weight
                tr, pos, aux_pos, wlog = grow(
                    bins_t, include, g, h, fmask, aux=aux_bins,
                    key=jax.random.fold_in(kg, grp),
                )
                if goss_on:
                    pos_train, aux_pos = aux_pos[0], aux_pos[1:]
                else:
                    pos_train = pos
                if refine_lad:
                    tr = _lad_refine_device(
                        tr, pos_train, y, scores, weight, real_mask,
                        p.learning_rate,
                    )
                with obs_scopes.scope("gbdt.score_update"):
                    add = leaf_of(tr.leaf, pos_train)
                    if K > 1:
                        scores = scores.at[:, grp].add(add)
                    else:
                        scores = scores + add
                    if has_test:
                        add_t = leaf_of(tr.leaf, aux_pos[0])
                        if K > 1:
                            scores_t = scores_t.at[:, grp].add(add_t)
                        else:
                            scores_t = scores_t + add_t
                t_idx = rnd * K + grp
                for name in (
                    "feat", "slot", "slot_r", "left", "right",
                    "leaf", "gain", "hess", "cnt",
                ):
                    arr = getattr(tr, name)
                    bufs[name] = bufs[name].at[t_idx].set(
                        arr.astype(bufs[name].dtype)
                    )
                bufs["n_nodes"] = bufs["n_nodes"].at[t_idx].set(tr.n_nodes)
                bufs["wlog"] = bufs["wlog"].at[t_idx].set(wlog)

            per = jnp.where(weight > 0, loss_fn.loss(scores, y), 0.0)
            loss_buf = loss_buf.at[rnd].set(
                jnp.sum(weight * per) / jnp.maximum(jnp.sum(weight), 1e-12)
            )
            if has_test:
                per_t = jnp.where(w_t > 0, loss_fn.loss(scores_t, y_t), 0.0)
                tloss_buf = tloss_buf.at[rnd].set(
                    jnp.sum(w_t * per_t) / jnp.maximum(jnp.sum(w_t), 1e-12)
                )
            return (scores, scores_t, bufs, loss_buf, tloss_buf)

        return jax.jit(round_step, donate_argnums=(0,))

    def _build_round_step(self, dd: "_DevInputs", spec: GrowSpec, has_test: bool):
        ranges = None
        if self._efb_plan is not None:
            ranges = self._efb_plan.range_tables(dd.B, F_pad=dd.F_prog)
        grow = make_grow_tree(
            spec, mesh=self.mesh if dd.D > 1 else None, ranges=ranges
        )
        return self._make_round_step(dd, grow, has_test, spec)

    # contract: the benchmark wraps this on the instance (and its planted
    # faults on the class) to time and count the compiled round program's
    # calls: (jit_round, carry, data, start_round) -> the callable that
    # _run_rounds calls as f(carry, rnd, key, data)
    def _probe_compile(self, jit_round, carry, data, start_round: int):
        """AOT-compile the round program once; the compiled object is
        reused for every round, so this is not a second compile. A
        Mosaic/XLA failure raises: a kernel that does not compile is
        repaired, never routed around. The compiled HLO is where the
        scope map (which instruction is `gbdt.hist`, ...) is read from."""
        return obs_scopes.compile_lowered(jit_round.lower(
            carry,
            jnp.asarray(start_round),
            jax.random.fold_in(jax.random.PRNGKey(20170425), start_round),
            data,
        ))

    def _export_wave_stats(self, ts: dict, dd: "_DevInputs", spec: GrowSpec):
        """Analytic device-cost totals from the engine's wave log — the
        inputs to the bench's achieved-vs-peak MXU/HBM accounting and the
        O(wave rows) ablation record. The model counts the dominant device
        work only (histogram one-hot matmuls + routing traffic); split
        enumeration and score updates are O(nodes) / O(n) per ROUND and
        small beside them."""
        wl = self.wave_log  # (T, MW, 5)
        used = wl[..., 3] > 0
        F, B = dd.F_prog, dd.B
        bins_bytes = 1 if dd.B <= 256 else 4
        rows_scanned = float((wl[..., 0] * used).sum())
        trees_used = used.any(axis=-1)
        n_trees = float(trees_used.sum())
        goss_on = 0.0 < spec.goss_a < 1.0
        ts["hist_passes"] = float(used.sum())
        ts["hist_rows_scanned"] = rows_scanned
        ts["hist_rows_needed"] = float((wl[..., 1] * used).sum())
        # one-hot accumulation: rows x (3 * width) x B MACs per feature
        ts["hist_macs"] = float(
            (wl[..., 0] * 3.0 * wl[..., 3] * used).sum()
        ) * B * F
        # histogram pass traffic: bins row + pos/g/h per scanned row
        ts["hist_bytes"] = rows_scanned * (F * bins_bytes + 12)
        # routing: every wave re-reads each row's bins + pos, writes pos
        # (root pass routes nothing). Per-DEVICE rows, matching the wave
        # log's per-shard units and the single-chip peak comparison. The
        # fit-matrix width comes from each tree's root pass (== n per
        # shard unsampled, the compacted width under GOSS); with GOSS the
        # full train matrix ALSO routes every wave as an aux set for the
        # final leaf assignment.
        rows_per_device = dd.n_score / max(dd.D, 1)
        fit_rows = wl[:, 0, 0]  # (T,) per-shard fit width per tree
        route_waves_t = np.maximum(used.sum(axis=-1) - 1, 0)
        routed_rows = fit_rows + (rows_per_device if goss_on else 0.0)
        ts["route_bytes"] = float(
            (route_waves_t * routed_rows * trees_used).sum()
        ) * (F * bins_bytes + 8)
        # read from the table the engine builds the growth program from
        n_dev = dd.n_score // max(dd.D, 1)
        rungs = spec.rungs(spec.goss_sizes(n_dev)[2] if goss_on else n_dev)
        ts["partition"] = bool(rungs)
        ts["fused"] = any(impl == "fused" for _, impl in rungs)
        ts["leaf_lookup_kernel"] = (
            spec.leaf_lookup(LEAF_KERNEL_MAX_NODES) == "pallas"
        )
        # what the width chose (_grow_spec, GrowSpec.route / .packed), and
        # what it costs in memory, from shapes
        ts["features"] = int(F)
        ts["hist_pool_bytes"] = int(
            spec.max_nodes * (F // max(dd.D, 1)) * B * 3 * 4
        )
        ts["route_kernel"] = spec.route == "pallas"
        ts["packed_tiles"] = bool(spec.packed)
        ts["rungs_fused"] = sum(impl == "fused" for _, impl in rungs)
        ts["rungs_xla"] = sum(impl == "xla" for _, impl in rungs)
        ts["hist_factored_passes"] = spec.factored_passes()
        # the partitioned passes: those that scanned a budget, not the fit
        # rows their tree's root pass scanned
        part = used & (wl[..., 0] < wl[:, :1, 0])
        ts["trees_logged"] = n_trees
        ts["hist_part_passes"] = float(part.sum())
        ts["hist_part_rows_scanned"] = float((wl[..., 0] * part).sum())
        ts["hist_part_rows_needed"] = float((wl[..., 1] * part).sum())
        ts["goss"] = goss_on
        if goss_on:
            ts["goss_a"] = float(spec.goss_a)
            ts["goss_b"] = float(spec.goss_b)
            # per-shard GOSS-kept rows per tree (wave-log col 4, constant
            # within a tree) — the sampled-rows evidence next to
            # scanned/needed
            ts["goss_rows_per_tree"] = float(
                (wl[:, 0, 4] * trees_used).sum() / max(n_trees, 1.0)
            )
        self._publish_wave_obs(wl, used, goss_on)

    def _publish_wave_obs(self, wl, used, goss_on: bool = False) -> None:
        """Accumulate the wave log into obs counters ONCE PER TREE (the
        registry is the shared source bench and any report reads; the
        per-tree granularity keeps tree-level events available without a
        second device fetch — `wl` is the single end-of-run fetch)."""
        if not obs_enabled():
            return
        for t in range(wl.shape[0]):
            u = used[t]
            waves = float(u.sum())
            if not waves:
                continue
            scanned = float((wl[t, :, 0] * u).sum())
            needed = float((wl[t, :, 1] * u).sum())
            splits = float((wl[t, :, 2] * u).sum())
            sampled = float(wl[t, 0, 4])
            obs_inc("gbdt.trees")
            obs_inc("gbdt.waves", waves)
            obs_inc("gbdt.hist_rows_scanned", scanned)
            obs_inc("gbdt.hist_rows_needed", needed)
            obs_inc("gbdt.splits", splits)
            if goss_on:
                obs_inc("gbdt.goss.trees")
                obs_inc("gbdt.goss.rows_sampled", sampled)
            obs_event(
                "gbdt.tree", tree=t, waves=waves, rows_scanned=scanned,
                rows_needed=needed, splits=splits, rows_sampled=sampled,
            )

    # contract: the benchmark wraps this on the instance and reads the
    # carry it returns: [2]["wlog"] the wave log, [3] the per-round losses
    def _run_rounds(
        self, jit_round, carry, data, dd, model, feature_names,
        start_round: int, has_test: bool, t0: float, ts: dict,
    ):
        """Enqueue the round programs with lagged sync + periodic dumps.

        Lagged sync: fetching the CURRENT round's loss is a device->host
        sync that stalls the enqueue pipeline until that round finishes. At
        each sync point we enqueue a tiny on-device slice of the loss and
        materialize it one sync window LATER — by then it completed long
        ago, so the float() costs host time only, with zero device idle
        (the queue stays ~2 windows deep; watch mode keeps the synchronous
        path since its metric evals fetch eagerly anyway). What a sync
        costs is measured: a `gbdt.sync` span lasts as long as the host
        stood waiting for the device (counter `gbdt.sync_wait_s`), and the
        ends of two successive ones are a device-settled sync window."""
        p = self.params
        K = self.K
        root_key = jax.random.PRNGKey(20170425)
        sync_every = max(1, (p.round_num - start_round) // 20)
        watch_eval = (
            EvalSet(p.eval_metric, K=max(K, 2))
            if p.eval_metric and (p.watch_train or p.watch_test)
            else None
        )
        self.sync_log: List[Tuple[int, float]] = []  # (round, wall s) at syncs
        # retrace alarm: the round program is AOT-compiled, so any XLA
        # compile counted after the FIRST sync (warmup: eval/predict jits)
        # is an unexpected recompilation — a retrace storm shows up here
        # instead of as silently-tripled round times
        self._retrace = health.RetraceSentinel("gbdt.rounds")
        # retrace culprit vocabulary: the sentinel arms/checks with the
        # CURRENT round-call signature (late-binding closure over `carry`)
        # so a fired health.retrace names the argument/dim that moved;
        # computed only at sync cadence, and only with ytkprof on
        self._retrace_sig = (
            (lambda: profiler.abstract_signature(carry, data))
            if profiler.enabled()
            else None
        )
        profile_dir = knobs.get_str("YTK_PROFILE_DIR")
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
        self._t_train0 = time.time()
        pending: Optional[
            Tuple[int, jnp.ndarray, Optional[jnp.ndarray], float]
        ] = None
        synced = start_round - 1  # last round a sync point was taken at
        for rnd in range(start_round, p.round_num):
            if self._guard is not None and self._guard.triggered:
                # round boundary = the safe preemption point: fetching the
                # tree buffers drains every enqueued round, so the dump
                # holds exactly the completed rounds and the resumed run
                # re-enters at `rnd` bit-identically (round-indexed RNG)
                with obs_span("gbdt.preempt", step=rnd):
                    self._preempt_checkpoint(
                        model, carry[2], dd.bins, feature_names, rnd
                    )
            # enqueue-side span: the round program is async, so this
            # measures dispatch (device time shows up in the sync spans)
            with obs_step_span("gbdt.round", rnd, round=rnd), profiler.LEDGER.program(
                "gbdt.round",
                sig_fn=lambda: profiler.abstract_signature(carry, data),
            ):
                rnd_dev = jnp.asarray(rnd)
                obs_inc("launches.jit_round_step")
                carry = jit_round(
                    carry, rnd_dev, jax.random.fold_in(root_key, rnd), data
                )
            obs_inc("gbdt.rounds")
            if (rnd + 1) % sync_every == 0 or rnd == p.round_num - 1:
                if watch_eval is None:
                    # the span holds the slices' dispatch (and, the first
                    # time, their compile) and the host's wait for the
                    # loss enqueued one window earlier
                    with _sync_span(rnd, rounds=rnd - synced, lagged=True) as sp:
                        obs_inc("launches.jit_sync_slice", 2 if has_test else 1)
                        nxt = (
                            rnd,
                            sync_slice(carry[3], rnd_dev),
                            sync_slice(carry[4], rnd_dev) if has_test else None,
                            time.time(),  # sync-point host time, not emission
                        )
                        if pending is not None:
                            sp.add(round=pending[0])
                            self._emit_sync(pending, t0)
                        pending = nxt
                else:
                    self._sync_report(rnd, carry, dd, watch_eval, t0, rnd - synced)
                synced = rnd
            if p.model.dump_freq > 0 and (rnd + 1) % p.model.dump_freq == 0:
                self._append_trees_from_bufs(
                    model, carry[2], dd.bins, feature_names,
                    len(model.trees), (rnd + 1) * K,
                )
                self._dump_model(model)
        if pending is not None:
            with _sync_span(pending[0], round=pending[0], rounds=0, lagged=True):
                self._emit_sync(pending, t0)

        if profile_dir:
            jax.block_until_ready(carry[0])
            jax.profiler.stop_trace()
            log.info("jax profiler trace written to %s", profile_dir)
        ts["train"] = time.time() - self._t_train0
        if self.sync_log:
            # skip the first sync window: it absorbs the one-time XLA compile
            r0, s0 = self.sync_log[1] if len(self.sync_log) >= 3 else self.sync_log[0]
            r1, s1 = self.sync_log[-1]
            if r1 > r0:
                ts["trees_per_sec_steady"] = (r1 - r0) * K / max(s1 - s0, 1e-9)
        return carry

    def _train_device(
        self, train: Optional[GBDTData], test: Optional[GBDTData]
    ) -> GBDTResult:
        p = self.params
        t0 = time.time()
        ts = self.time_stats = {}  # TimeStats equivalent (data/gbdt/TimeStats.java)
        recorder.auto_install()
        recorder.set_config_fingerprint(p)
        health.install_trace_counters()
        if train is None:
            with profiler.phase("gbdt.load"):
                train, test = GBDTIngest(p, self.fs).load()
        ts["load"] = time.time() - t0
        health.record_memory("gbdt.load")
        K = self.K

        dd = None
        with profiler.phase(
            "gbdt.preprocess", settle=lambda: dd.device_arrays(),
            F=train.n_features,
            # ranges of columns the device-side binning goes through
            chunks=-(-train.n_features
                     // feature_chunk(train.n_features, train.X.shape[0])),
        ):
            dd = self._prep_device_inputs(train, test)
        health.record_memory("gbdt.preprocess")
        bins = dd.bins
        y, weight, y_t, w_t = dd.y, dd.weight, dd.y_t, dd.w_t
        # contract: the benchmark reads time_stats["preprocess"]
        ts["preprocess"] = time.time() - t0 - ts["load"]
        log.info("load+preprocess %.1fs", time.time() - t0)

        # everything between binning and the compile (grow spec, base
        # score, resume, initial scores, tree buffers, the round step's
        # construction) under one device-settled span
        scores = scores_t = bufs = loss_buf = tloss_buf = None
        with profiler.phase(
            "gbdt.prepare",
            settle=lambda: (scores, scores_t, bufs, loss_buf, tloss_buf),
        ):
            # GOSS sizing discounts sample-axis padding (real-row fraction
            # of the per-process padded shard; top_k needs a static k, so
            # the engine can't count real rows itself)
            n_pad_local = dd.n_score // max(jax.process_count(), 1)
            goss_scale = min(1.0, train.n_real / max(n_pad_local, 1))
            spec = self._grow_spec(dd.F_prog, dd.B, goss_scale=goss_scale)
            self._wave_ctx = (dd, spec)  # what the stop path publishes with

            base_np = self._base_score(train, K)
            model = GBDTModel(
                base_prediction=float(np.mean(base_np)),
                num_tree_in_group=K,
                obj_name=self.loss.name,
            )
            model, start_round = self._load_resume_model(
                model, K, feature_names=train.feature_names
            )
            scores, scores_t = self._init_device_scores(model, dd, base_np)
            bufs, loss_buf, tloss_buf = self._make_tree_bufs(spec.max_nodes)

            has_test = test is not None
            # big arrays ride as explicit args (closure capture would bake
            # them into the program as constants); test arrays fold into
            # `data`
            data = (dd.bins_t, y, weight, dd.real_mask) + (
                (dd.aux_bins[0], y_t, w_t) if has_test else ()
            )
            jit_round = self._build_round_step(dd, spec, has_test)

        if p.just_evaluate:
            return self._finalize_device(
                model, bins, scores, y, weight, scores_t, y_t, w_t,
                bufs, loss_buf, tloss_buf, start_round, train.feature_names, t0,
                trained_rounds=start_round,
            )

        carry = (scores, scores_t, bufs, loss_buf, tloss_buf)
        # compile probe gets its own phase (it dominates short runs —
        # without it the ytkprof wall-time decomposition can't hit its
        # coverage bar) and a ledger label so every backend compile of
        # the round program lands named, with its argument signature
        with profiler.phase("gbdt.compile"), profiler.LEDGER.program(
            "gbdt.round",
            sig_fn=lambda: profiler.abstract_signature(carry, data),
        ):
            jit_round = self._probe_compile(
                jit_round, carry, data, start_round
            )
        with profiler.phase(
            "gbdt.train", capture=True, rounds=p.round_num - start_round
        ):
            carry = self._run_rounds(
                jit_round, carry, data, dd, model, train.feature_names,
                start_round, has_test, t0, ts,
            )
        health.record_memory("gbdt.train")
        scores, scores_t, bufs, loss_buf, tloss_buf = carry
        self.wave_log = np.asarray(jax.device_get(bufs["wlog"]))
        self._export_wave_stats(ts, dd, spec)
        t_fin = time.time()
        with profiler.phase("gbdt.finalize"):
            out = self._finalize_device(
                model, bins, scores, y, weight, scores_t, y_t, w_t,
                bufs, loss_buf, tloss_buf, start_round, train.feature_names, t0,
                trained_rounds=p.round_num,
            )
        ts["finalize"] = time.time() - t_fin
        health.record_memory("gbdt.finalize")
        log.info(
            "[time stats] load=%.1fs preprocess=%.1fs train=%.1fs "
            "finalize=%.1fs%s",
            ts["load"], ts["preprocess"], ts["train"], ts["finalize"],
            (
                f" steady={ts['trees_per_sec_steady']:.2f} trees/s"
                if "trees_per_sec_steady" in ts else ""
            ),
        )
        self._publish_time_stats(ts)
        return out

    @staticmethod
    def _publish_time_stats(ts: dict) -> None:
        """Mirror every scalar time_stat into the registry (gbdt.stat.*) —
        the ONE snapshot bench roofline accounting reads, so benchmarks
        and production runs report from the same source of truth."""
        for k, v in ts.items():
            if isinstance(v, (bool, int, float)):
                obs_gauge(f"gbdt.stat.{k}", float(v))

    def _health_sync(self, rnd: int, tl: float) -> None:
        """Sentinels at a pipeline sync: NaN/inf train loss (strict mode
        aborts the run with the flight-dump path) and the unexpected-retrace
        alarm — armed at the first sync, checked at every later one."""
        if not health.enabled():
            return
        health.check_loss("gbdt.sync", tl, round=rnd)
        sig_fn = getattr(self, "_retrace_sig", None)
        sig = sig_fn() if sig_fn is not None else None
        if self._retrace.baseline is None:
            self._retrace.arm(sig=sig)
        else:
            self._retrace.check(sig=sig, round=rnd)

    # contract: the benchmark wraps this on the instance to close its
    # window where a SIGTERM stops the run: (model, bufs, bins, names, rnd)
    def _preempt_checkpoint(self, model, bufs, bins, names, rnd: int) -> None:
        """Emergency checkpoint at round boundary `rnd`, then Preempted.
        The wave-log counters and the `gbdt.stat.*` gauges are published
        first, through the functions the normal end uses: a stopped run's
        registry says what it did."""
        self._append_trees_from_bufs(
            model, bufs, bins, names, len(model.trees), rnd * self.K
        )
        self._dump_model(model)
        ts = self.time_stats
        ts["train"] = time.time() - self._t_train0
        self.wave_log = np.asarray(jax.device_get(bufs["wlog"]))
        self._export_wave_stats(ts, *self._wave_ctx)
        self._publish_time_stats(ts)
        if knobs.get_str("YTK_PROFILE_DIR"):
            # the Preempted raise skips the post-loop stop_trace: close the
            # profiler here or the very run being profiled loses its trace
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                log.warning("profiler stop at preemption failed: %s", e)
        self._guard.preempt(
            self.params.model.data_path, family="gbdt", rounds=rnd,
            trees=len(model.trees),
        )

    def _emit_sync(self, pending, t0) -> None:
        """Materialize a lagged sync record (round, loss slice[, test]).
        The logged time is the round's sync-point host timestamp carried in
        `pending` — emission happens one window later, which would skew
        absolute per-round times late (steady-state trees/s uses diffs and
        is insensitive either way)."""
        chaos_point("gbdt.sync")
        rnd, loss_dev, tloss_dev, t_sync = pending
        obs_inc("gbdt.syncs")
        tl = float(loss_dev)  # completed a window ago: one RTT, no stall
        self._health_sync(rnd, tl)
        elapsed = t_sync - t0
        self.sync_log.append((rnd, elapsed))
        msg = f"[round={rnd}] {elapsed:.1f}s train loss={tl:.6f}"
        if tloss_dev is not None:
            msg += f" test loss={float(tloss_dev):.6f}"
        log.info(msg)

    def _sync_report(
        self, rnd: int, carry, dd: "_DevInputs", watch_eval, t0, rounds: int,
    ):
        """Pipeline sync + progress line (+ watch-flag metrics at sync
        points — reference: EvalSet per round when watch_train/watch_test;
        here per sync so the enqueue pipeline stays deep between syncs).
        The final round skips the watch log: _finalize_device evaluates
        the same final scores anyway."""
        p = self.params
        chaos_point("gbdt.sync")
        obs_inc("gbdt.syncs")
        with _sync_span(rnd, round=rnd, rounds=rounds, lagged=False):
            tl = float(carry[3][rnd])  # syncs the pipeline
        self._health_sync(rnd, tl)
        elapsed = time.time() - t0
        self.sync_log.append((rnd, elapsed))
        msg = f"[round={rnd}] {elapsed:.1f}s train loss={tl:.6f}"
        has_test = dd.y_t is not None
        if has_test:
            msg += f" test loss={float(carry[4][rnd]):.6f}"
        if watch_eval is not None and rnd != p.round_num - 1:
            if p.watch_train:
                m = watch_eval.evaluate(
                    self.loss.predict(carry[0]), dd.y, dd.weight
                )
                msg += " train " + " ".join(f"{k}={v:.6f}" for k, v in m.items())
            if p.watch_test and has_test:
                m = watch_eval.evaluate(
                    self.loss.predict(carry[1]), dd.y_t, dd.w_t
                )
                msg += " test " + " ".join(f"{k}={v:.6f}" for k, v in m.items())
        log.info(msg)

    def _base_score(self, train: GBDTData, K: int):
        p = self.params
        if p.sample_dependent_base_prediction:
            if jax.process_count() > 1:
                # global weighted label mean across process shards
                from ..parallel.collectives import host_allgather_objects

                w = train.weight[: train.n_real]
                y = np.asarray(train.y[: train.n_real])
                wy = (
                    (w[:, None] * y).sum(axis=0) if K > 1 else float(np.dot(w, y))
                )
                merged = host_allgather_objects((wy, float(np.sum(w))))
                tot_wy = np.sum([m[0] for m in merged], axis=0)
                tot_w = float(np.sum([m[1] for m in merged]))
                mean = tot_wy / max(tot_w, 1e-12)
                if K > 1:
                    return np.asarray(
                        self.loss.pred2score(jnp.asarray(mean)), np.float32
                    )
                return np.float32(self.loss.pred2score(float(mean)))
            if K > 1:
                mean = np.average(
                    np.asarray(train.y[: train.n_real]),
                    axis=0,
                    weights=np.asarray(train.weight[: train.n_real]),
                )
                return np.asarray(self.loss.pred2score(jnp.asarray(mean)), np.float32)
            mean = float(
                np.average(
                    train.y[: train.n_real], weights=train.weight[: train.n_real]
                )
            )
            return np.float32(self.loss.pred2score(mean))
        return np.float32(self.loss.pred2score(p.uniform_base_prediction))

    def _append_trees_from_bufs(
        self, model: GBDTModel, bufs, bins: FeatureBins, names, have: int, want: int
    ) -> None:
        """Convert device tree buffers [have, want) into host Trees."""
        if want <= have:
            return
        # slice on device first: dump_freq checkpoints fetch only the new
        # trees, not the whole (T, M) run buffers; one batched device_get
        # instead of 10 sequential device->host fetches
        host = jax.device_get({k: v[have:want] for k, v in bufs.items()})
        for i in range(want - have):
            tree = self._arrays_to_tree(
                {k: v[i] for k, v in host.items()}, bins, names
            )
            # tree sanity on the already-fetched host arrays: an empty tree
            # means boosting stopped learning; a NaN gain means the split
            # statistics went rotten on device
            health.check_tree("gbdt.tree", len(tree.gain), tree.gain, tree=have + i)
            model.trees.append(tree)

    def _arrays_to_tree(self, d: Dict[str, np.ndarray], bins, names) -> Tree:
        nn = int(d["n_nodes"])
        t = Tree()
        t.feat = [int(v) for v in d["feat"][:nn]]
        t.slot = [int(v) for v in d["slot"][:nn]]
        t.split = [float(v) for v in d["slot_r"][:nn]]  # slot-space pre-convert
        t.left = [int(v) for v in d["left"][:nn]]
        t.right = [int(v) for v in d["right"][:nn]]
        t.default_left = [True] * nn
        t.leaf_value = [float(v) for v in d["leaf"][:nn]]
        t.gain = [float(v) for v in d["gain"][:nn]]
        t.hess_sum = [float(v) for v in d["hess"][:nn]]
        t.sample_cnt = [int(round(float(v))) for v in d["cnt"][:nn]]
        if self._efb_plan is not None:
            # bundle-space (column, slot interval) -> original feature +
            # bin interval, BEFORE names and value conversion, so the
            # dumped model is indistinguishable from an unbundled run
            unbundle_tree(t, self._efb_plan)
        t.feat_name = [
            (names[f] if (names and 0 <= f < len(names)) else str(f)) if f >= 0 else ""
            for f in t.feat
        ]
        self._convert_tree(t, bins)
        return t

    def _finalize_device(
        self, model, bins, scores, y, weight, scores_t, y_t, w_t,
        bufs, loss_buf, tloss_buf, start_round, names, t0,
        trained_rounds: int,
    ) -> GBDTResult:
        p = self.params
        K = self.K
        self._append_trees_from_bufs(
            model, bufs, bins, names, len(model.trees), trained_rounds * K
        )
        if not p.just_evaluate:
            # held-out predictions (else train) feed the quality
            # sidecar's score block before the final dump lands
            if scores_t is not None:
                self._stash_quality_scores(scores_t, w_t)
            else:
                self._stash_quality_scores(scores, weight)
            self._dump_model(model)

        eval_set = EvalSet(p.eval_metric, K=max(K, 2)) if p.eval_metric else None
        res = GBDTResult(
            model=model,
            train_loss=float(_wavg_loss(self.loss, scores, y, weight)),
            test_loss=(
                float(_wavg_loss(self.loss, scores_t, y_t, w_t))
                if scores_t is not None
                else None
            ),
        )
        loss_np = np.asarray(loss_buf)
        tloss_np = np.asarray(tloss_buf)
        for rnd in range(start_round, trained_rounds):
            rec = {"round": rnd, "train_loss": float(loss_np[rnd])}
            if scores_t is not None:
                rec["test_loss"] = float(tloss_np[rnd])
            res.round_log.append(rec)
        if eval_set is not None:
            res.train_metrics = eval_set.evaluate(
                self.loss.predict(scores), y, weight
            )
            if scores_t is not None:
                res.test_metrics = eval_set.evaluate(
                    self.loss.predict(scores_t), y_t, w_t
                )
        log.info(
            "training done in %.1fs: %d trees, train loss %.6f%s",
            time.time() - t0,
            len(model.trees),
            res.train_loss,
            f", test loss {res.test_loss:.6f}" if res.test_loss is not None else "",
        )
        return res

    # -- helpers ----------------------------------------------------------

    def _convert_tree(self, tree: Tree, bins: FeatureBins) -> None:
        """Slot interval -> real split value + default direction
        (reference: GBDTOptimizer.convertModel:669 + addDefaultDirection)."""
        st = self.params.split_type
        for nid in range(tree.n_nodes()):
            if tree.is_leaf(nid):
                continue
            fid = tree.feat[nid]
            cond = bins.split_value(
                fid, tree.slot[nid], int(tree.split[nid]), split_type=st
            )
            tree.split[nid] = cond
            # missing-value default direction from the fill value
            fill = self._missing_fill
            if fill is not None:
                tree.default_left[nid] = bool(fill[fid] <= cond)

    _missing_fill: Optional[np.ndarray] = None
    _efb_plan = None  # BundlePlan when EFB merged columns this run
    _bins_sidecar = None  # (feature names, FeatureBins) for the serve sidecar
    _quality_features = None  # `<model>.sketch.json` feature block (obs/quality)
    _quality_scores = None  # held-out predictions for the sidecar score block
    _replay_bins = None  # transient pre-bundle matrices for warm-start replay
    _guard = None  # PreemptionGuard while train() runs (resilience/preempt.py)

    def _tree_scores_from_raw(self, tree: Tree, bins: FeatureBins, bins_dev):
        """Score a converted (value-space) tree against the bin matrix by
        re-deriving slot thresholds: bin b goes left iff its representative
        value <= cond."""
        feat = np.asarray(tree.feat, np.int32)
        slot = np.full(tree.n_nodes(), -1, np.int32)
        for nid in range(tree.n_nodes()):
            if tree.is_leaf(nid):
                continue
            fid = tree.feat[nid]
            cnt = int(bins.counts[fid])
            v = bins.values[fid, :cnt]
            slot[nid] = int(np.searchsorted(v, tree.split[nid], side="right")) - 1
        depth = max(tree.max_depth(), 1)
        return _traverse_kernel(
            bins_dev,
            jnp.asarray(feat),
            jnp.asarray(slot),
            jnp.asarray(np.asarray(tree.left, np.int32)),
            jnp.asarray(np.asarray(tree.right, np.int32)),
            jnp.asarray(np.asarray(tree.leaf_value, np.float32)),
            depth,
        )

    def _build_quality_features(self, train) -> Optional[dict]:
        """Feature block of the `<model>.sketch.json` quality sidecar
        (obs/quality.py): per-feature GK summaries + presence rates of
        the (real-row) training matrix, built once at binning time while
        the host matrix is still alive."""
        names = list(train.feature_names or [])
        if not names:
            return None
        from ..obs.quality import build_training_sketch

        n_real = getattr(train, "n_real", None) or train.X.shape[0]
        with obs_span("gbdt.quality_sketch", features=len(names)):
            return build_training_sketch(
                train.X, names, weight=np.asarray(train.weight[:n_real]),
                rows=n_real,
            )

    def _stash_quality_scores(self, scores, weight) -> None:
        """Score distribution for the quality sidecar: predictions of the
        trained ensemble over the held-out set when one exists (else the
        training rows), padded/zero-weight rows excluded."""
        try:
            preds = np.asarray(self.loss.predict(scores))
            w = np.asarray(weight)[: preds.shape[0]]
            self._quality_scores = preds[w > 0]
        except Exception as e:  # noqa: BLE001 — sidecar evidence, never the run
            log.warning("quality score stash failed (%s: %s); the sketch "
                        "sidecar will carry no score block",
                        type(e).__name__, e)

    def _dump_model(self, model: GBDTModel) -> None:
        if jax.process_index() != 0:
            return  # rank0-only dump (reference: GBDTOptimizer.java:434-437)
        p = self.params
        model_text = model.dumps(with_stats=True)
        from .binning import model_text_digest

        digest = model_text_digest(model_text)
        if self._bins_sidecar is not None:
            # bin-edge sidecar for serve-side binned scoring — written
            # BEFORE the model so a fingerprint-watch reload (triggered by
            # the model file) always finds edges at least as fresh; the
            # embedded digest of the model text about to land lets serving
            # reject the new-edges/old-model pairing a crash between the
            # two writes would leave behind
            from .binning import bin_edges_path, dump_bin_edges

            names, bins = self._bins_sidecar
            if len(names) == len(bins.counts):
                dump_bin_edges(
                    self.fs, bin_edges_path(p.model.data_path), names, bins,
                    split_type=p.split_type,
                    model_digest=digest,
                )
        if self._quality_features is not None:
            # model-quality sidecar (`<model>.sketch.json`, obs/quality.py):
            # per-feature training sketches + (once training finished) the
            # held-out score distribution — written BEFORE the model like
            # `.bins.json`, so a fingerprint-watch reload never pairs a
            # fresh ensemble with a stale drift baseline
            from ..obs.quality import (
                build_score_block,
                dump_quality_sidecar,
                quality_sidecar_path,
            )

            payload = dict(self._quality_features)
            if self._quality_scores is not None:
                payload["score"] = build_score_block(self._quality_scores)
            dump_quality_sidecar(
                self.fs, quality_sidecar_path(p.model.data_path), payload,
                model_digest=digest,
            )
        # atomic write-then-replace: the serving registry hot-reloads this
        # file on a fingerprint watch, so a reader must never see a
        # half-written ensemble
        with self.fs.atomic_open(p.model.data_path) as f:
            f.write(model_text)
        if p.model.feature_importance_path:
            # reference format: header + name\tsum_split_count\tsum_gain
            # (dataflow/GBDTDataFlow.dumpFeatureImportance:397-415)
            imp = model.feature_importance()
            with self.fs.atomic_open(p.model.feature_importance_path) as f:
                f.write("feature_name\tsum_split_count\tsum_gain\n")
                for name, (cnt, gain) in imp.items():
                    f.write(f"{name}\t{cnt}\t{gain}\n")

    def _finalize(
        self, model, scores, y, weight, test_state, eval_set, round_log, bins
    ) -> GBDTResult:
        res = GBDTResult(
            model=model,
            train_loss=float(_wavg_loss(self.loss, scores, y, weight)),
            test_loss=None,
            round_log=round_log,
        )
        if eval_set is not None:
            res.train_metrics = eval_set.evaluate(self.loss.predict(scores), y, weight)
        if test_state is not None:
            _, y_t, w_t, scores_t = test_state
            res.test_loss = float(_wavg_loss(self.loss, scores_t, y_t, w_t))
            if eval_set is not None:
                res.test_metrics = eval_set.evaluate(
                    self.loss.predict(scores_t), y_t, w_t
                )
        return res


_LAD_Q = 4096  # rank-grid resolution for device LAD refine


def _lad_refine_device(tr, pos, y, scores, weight, real_mask, lr):
    """Approximate LAD leaf refinement inside the device round: leaf value =
    lr * weighted median of (y - score) over the leaf's rows, medians taken
    on a global rank grid of _LAD_Q sorted residuals (reference:
    optimizer/gbdt/TreeRefiner.java approximate GK mode; grid quantization
    replaces the sketch — exact when n <= _LAD_Q). One sort + one
    scatter-add per tree, no host round-trip."""
    M = tr.leaf.shape[0]
    Q = _LAD_Q
    r = y - scores
    valid = real_mask & (weight > 0)
    big = jnp.float32(3.4e38)
    rs = jnp.sort(jnp.where(valid, r, big))
    nv = jnp.sum(valid.astype(jnp.int32))
    # ranks = i*(nv-1)//(Q-1) in pure i32: i*base + i*rem//(Q-1) avoids the
    # i*(nv-1) product overflowing at n > ~500k
    i = jnp.arange(Q, dtype=jnp.int32)
    span = jnp.maximum(nv - 1, 0)
    base, rem = span // (Q - 1), span % (Q - 1)
    ranks = i * base + (i * rem) // (Q - 1)
    grid = rs[ranks]
    qi = jnp.clip(jnp.searchsorted(grid, r, side="right") - 1, 0, Q - 1)
    flat = pos * Q + qi
    w = jnp.where(valid, weight, 0.0)
    hist = jnp.zeros((M * Q,), jnp.float32).at[flat].add(w, mode="drop")
    cw = jnp.cumsum(hist.reshape(M, Q), axis=1)
    tot = cw[:, -1]
    med = grid[jnp.argmax(cw >= 0.5 * tot[:, None], axis=1)]
    is_leaf = (tr.feat == -1) & (jnp.arange(M) < tr.n_nodes)
    return tr._replace(
        leaf=jnp.where(is_leaf & (tot > 0), med * lr, tr.leaf)
    )


def _pad0(arr: np.ndarray, n_pad: int) -> np.ndarray:
    n = arr.shape[0]
    if n == n_pad:
        return arr
    return np.pad(arr, ((0, n_pad - n),) + ((0, 0),) * (arr.ndim - 1))
