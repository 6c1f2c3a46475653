"""Feature binning — samplers + value->bin conversion.

Rebuild of reference feature/gbdt/approximate/* (SampleManager + 5 samplers)
and data/gbdt/FeatureApprData.java:179 (convertFeaVal2ApprFeaIndex).

Bins are *representative values*: each feature's sampler emits a set of
candidate values, sorted; a raw value maps to the NEAREST representative
(last <=, then pulled down if closer to the previous one — exactly the
reference's BinarySearch.findLastEqualOrUpper + midpoint adjustment).
Split "slot s" means bins <= s go left; the dumped split value is the
mean/median of the two adjacent representatives (feature/gbdt/FeatureSplitType.java).

Samplers (feature/gbdt/approximate/sampler/*):
  sample_by_quantile   weighted quantiles at max_cnt even ranks, weights
                       raised to alpha (SampleByQuantile.java:105); the
                       reference's distributed GK sketch becomes an exact
                       sort-based weighted quantile on device/host
  sample_by_cnt        distinct values; if too many, values at max_cnt
                       uniformly-sampled rows
  sample_by_rate       distinct values of a Bernoulli(sample_rate) row sample
                       (if distinct count > min_cnt)
  sample_by_precision  values rounded to dot_precision decimals after
                       optional log / min-max normalization, then inverted
  no_sample            all distinct values (exact greedy)
"""

from __future__ import annotations

import fnmatch
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import knobs
from ..config.params import ApproximateSpec, GBDTParams

# Columns longer than this stream through the weighted GK sketch instead
# of the full-sort quantile path (sort+cumsum temporaries cost ~4x the
# column; the sketch is O(b log(n/chunk))). Override: YTK_SKETCH_ROWS.
SKETCH_ROWS = knobs.get_int("YTK_SKETCH_ROWS")

# The one byte budget of the device-side passes that grow with the column
# count (quantiles, bin ids, EFB's column statistics here; hist.tile_bins'
# widened copy): a 4-byte-a-cell copy of an (F, n) matrix is made whole up
# to WHOLE_BYTES (Higgs: 28 x 10.5M x 4 B = 1.18 GB), and past it (Epsilon:
# 2,000 x 400,000 x 4 B = 3.2 GB beside the raw rows) CHUNK_BYTES of
# columns at a time. Columns are independent in every one of those passes,
# so the chunked results are the whole matrix's bit for bit
# (tests/test_gbdt_wide.py).
WHOLE_BYTES = 2 << 30
CHUNK_BYTES = 1 << 28


def feature_chunk(F: int, n: int) -> int:
    """Columns of an (F, n) matrix of 4-byte cells worked on at a time: all
    F where the whole is at most WHOLE_BYTES, else equal parts of about
    CHUNK_BYTES (12 parts of 167 columns at 2,000 x 400,000)."""
    if F * n * 4 <= WHOLE_BYTES:
        return F
    parts = -(-(F * n * 4) // CHUNK_BYTES)
    return -(-F // parts)


class ColumnsT:
    """The raw (n, F) rows as the device-side binning reads them: (F, n)
    column-major, a range of columns at a time. Up to WHOLE_BYTES the
    transposed copy is made once and kept, and `chunks()` yields it whole;
    past that no transposed copy of the matrix exists and every pass
    transposes `feature_chunk` columns at a time."""

    def __init__(self, X):
        import jax
        import jax.numpy as jnp

        self.X = jax.device_put(X)
        self.n, self.F = self.X.shape
        self.step = feature_chunk(self.F, self.n)
        self.whole = jnp.transpose(self.X) if self.step >= self.F else None

    @property
    def n_chunks(self) -> int:
        return -(-self.F // self.step)

    def chunks(self):
        """(lo, hi, (hi - lo, n) device array) over all columns in order."""
        import jax.numpy as jnp

        if self.whole is not None:
            yield 0, self.F, self.whole
            return
        for lo in range(0, self.F, self.step):
            hi = min(lo + self.step, self.F)
            yield lo, hi, jnp.transpose(self.X[:, lo:hi])


@dataclass
class FeatureBins:
    """Per-feature sorted representative values, padded to a common width.

    values[f, :counts[f]] are real; padding slots repeat the last value so
    searchsorted stays monotone."""

    values: np.ndarray  # (F, B) f32 sorted per row
    counts: np.ndarray  # (F,) int32
    max_bins: int
    # exact[f]: the sampler kept every distinct value (all-distinct path);
    # None when unknown (device-built bins don't track it)
    exact: Optional[np.ndarray] = None

    def split_value(
        self, fid: int, lo: int, hi: Optional[int] = None,
        split_type: str = "mean",
    ) -> float:
        """Split cond for 'bins <= lo go left', where [lo, hi] is the split
        interval: last nonempty slot strictly before the boundary, and the
        boundary slot itself (reference: GBDTOptimizer.convertModel:669 +
        FeatureSplitType mean/median). hi=None means the adjacent interval
        [lo, lo+1]. The ONE split-value conversion — the trainer's tree
        conversion and any tooling must route through here (r3 Weak #3)."""
        v = self.values[fid]
        cnt = int(self.counts[fid])
        if hi is None:
            hi = lo + 1
        hi = min(hi, cnt - 1)  # boundary slots are nonempty, so < cnt; clamp
        if split_type == "median":
            s = lo + hi
            if s % 2 == 0:
                return float(v[s // 2])
            return 0.5 * (float(v[(s - 1) // 2]) + float(v[(s + 1) // 2]))
        return 0.5 * (float(v[lo]) + float(v[hi]))


def _sample_feature(
    col: np.ndarray, weight: np.ndarray, spec: ApproximateSpec, rng: np.random.RandomState
) -> Tuple[np.ndarray, bool]:
    """-> (sorted candidate values, kept-all-distinct flag)."""
    kind = spec.type
    if kind == "no_sample":
        return np.unique(col), True
    if kind == "sample_by_cnt":
        vals = np.unique(col)
        if len(vals) > spec.max_cnt:
            picks = rng.choice(len(col), size=spec.max_cnt, replace=False)
            return np.unique(col[picks]), False
        return vals, True
    if kind == "sample_by_rate":
        vals = np.unique(col)
        if len(vals) > spec.min_cnt:
            mask = rng.rand(len(col)) <= spec.sample_rate
            if mask.any():
                return np.unique(col[mask]), False
        return vals, True
    if kind == "sample_by_precision":
        x = col.astype(np.float64)
        lo = hi = None
        if spec.use_min_max:
            lo, hi = float(x.min()), float(x.max())
            x = (x - lo) / (hi - lo) if hi > lo else np.zeros_like(x)
        if spec.use_log:
            x = np.sign(x) * np.log1p(np.abs(x))
        r = np.unique(np.round(x, spec.dot_precision))
        # invert the normalization chain (reference: Sampler.reverse)
        if spec.use_log:
            r = np.sign(r) * (np.expm1(np.abs(r)))
        if spec.use_min_max and lo is not None and hi > lo:
            r = r * (hi - lo) + lo
        return np.unique(r.astype(np.float32)), False
    if kind == "sample_by_quantile":
        w = (
            np.power(np.maximum(weight, 0.0), spec.alpha)
            if spec.use_sample_weight
            else np.ones_like(col)
        )
        if len(col) > SKETCH_ROWS:
            # memory-bounded streaming path (reference: the GK sketch of
            # WeightApproximateQuantile.java behind SampleByQuantile) —
            # the full-sort temporaries below cost ~4x the column; the
            # sketch holds O(b log(n/chunk)) entries instead
            from .quantile_sketch import WeightedQuantileSketch

            sk = WeightedQuantileSketch(b=max(4 * spec.max_cnt, 256))
            cs = 1 << 22
            for i in range(0, len(col), cs):
                sk.push(col[i : i + cs], w[i : i + cs])
            # low-cardinality giant column: if no prune ever dropped
            # entries, the summary is a perfect distinct-value table —
            # keep the exact flag the sub-SKETCH_ROWS path would have set
            # (it buys the multihost merge the cheap exact-union path)
            summ = sk.summary()
            if sk.is_exact and summ.size <= spec.max_cnt:
                return summ.value.astype(np.float32), True
            return summ.query_values(spec.max_cnt), False
        vals = np.unique(col)
        if len(vals) <= spec.max_cnt:
            return vals, True
        order = np.argsort(col, kind="stable")
        sv, sw = col[order], w[order]
        cw = np.cumsum(sw)
        total = cw[-1]
        # max_cnt evenly spaced quantile ranks (the GK query points)
        ranks = (np.arange(1, spec.max_cnt + 1) / spec.max_cnt) * total
        pos = np.searchsorted(cw, ranks, side="left").clip(0, len(sv) - 1)
        return np.unique(sv[pos]), False
    raise ValueError(f"unknown sampler type: {kind!r}")


def _spec_for(fid: int, name: str, specs: Sequence[ApproximateSpec]) -> ApproximateSpec:
    """Column matching: `cols` is 'default' or a comma list of names/globs
    (reference: SampleManager sampler assignment)."""
    default = None
    for s in specs:
        if s.cols == "default":
            default = s
            continue
        for pat in str(s.cols).split(","):
            pat = pat.strip()
            if pat and (pat == name or fnmatch.fnmatch(name, pat)):
                return s
    return default or specs[0]


def build_bins(
    X: np.ndarray,
    weight: np.ndarray,
    params: GBDTParams,
    feature_names: Optional[Sequence[str]] = None,
    seed: int = 20170425,
) -> FeatureBins:
    """Run the configured sampler per feature; pad to a common bin width."""
    rng = np.random.RandomState(seed)
    F = X.shape[1]
    names = feature_names or [str(i) for i in range(F)]
    per_feature: List[np.ndarray] = []
    exact = np.zeros((F,), bool)
    for f in range(F):
        spec = _spec_for(f, names[f], params.approximate)
        vals, exact[f] = _sample_feature(X[:, f], weight, spec, rng)
        vals = vals.astype(np.float32)
        if len(vals) == 0:
            vals = np.zeros((1,), np.float32)
        per_feature.append(np.sort(vals))
    out = _to_feature_bins(per_feature)
    out.exact = exact
    return out


def _to_feature_bins(per_feature: List[np.ndarray]) -> "FeatureBins":
    """Pad per-feature sorted candidate lists to a common width (padding
    repeats the last value so searchsorted stays monotone)."""
    max_bins = max(len(v) for v in per_feature)
    F = len(per_feature)
    values = np.empty((F, max_bins), np.float32)
    counts = np.empty((F,), np.int32)
    for f, v in enumerate(per_feature):
        values[f, : len(v)] = v
        values[f, len(v):] = v[-1]
        counts[f] = len(v)
    return FeatureBins(values=values, counts=counts, max_bins=max_bins)


def merge_quantile_candidates(
    values_list: List[np.ndarray], mass_list: List[float], max_cnt: int
) -> np.ndarray:
    """Merge per-process quantile candidate sets into global candidates.

    Each process's candidates are (approximately) equal-mass quantile points
    of its local distribution, so the merged multiset with per-point mass
    total_i/len(values_i) is a compressed sketch of the global distribution;
    querying max_cnt even ranks of it is the TPU-host equivalent of the
    reference's GK summary merge + query (SampleManager.java:129-143,
    WeightApproximateQuantile.merge:476)."""
    vals = np.concatenate([np.asarray(v, np.float64) for v in values_list])
    mass = np.concatenate(
        [
            np.full(len(v), m / max(len(v), 1), np.float64)
            for v, m in zip(values_list, mass_list)
        ]
    )
    order = np.argsort(vals, kind="stable")
    sv, sm = vals[order], mass[order]
    cw = np.cumsum(sm)
    total = cw[-1]
    # midpoint rule: candidate k summarizes the local mass interval ending at
    # it, so its representative rank is the interval's center — without the
    # -mass/2 shift every merged quantile reads ~half a rank high
    ranks = (np.arange(1, max_cnt + 1) / max_cnt) * total
    pos = np.searchsorted(cw - 0.5 * sm, ranks, side="left").clip(0, len(sv) - 1)
    return np.unique(sv[pos].astype(np.float32))


def merge_bins_multihost(
    local: "FeatureBins",
    local_exact: np.ndarray,
    local_mass: np.ndarray,
    max_cnt_arr: np.ndarray,
    discrete: np.ndarray,
    local_summaries: Optional[Dict[int, "object"]] = None,
) -> "FeatureBins":
    """Cross-process merge of per-feature bin candidates.

    discrete[f]: non-quantile sampler — merges by uncapped set union (the
    allreduceMapSetUnion path of SampleManager.java:128; no_sample keeps
    exact-greedy semantics across hosts). Quantile features stay exact as a
    union while every process kept all distinct values AND the union fits
    that feature's max_cnt. Otherwise, when every process supplies a GK
    summary for the feature (local_summaries), the summaries merge with
    bounded rank error (the reference's Kryo'd Summary allreduce,
    SampleManager.java:129-143 + WeightApproximateQuantile.merge:476);
    the candidate-union approximation remains only as a fallback."""
    from ..parallel.collectives import host_allgather_objects

    payload = (
        [local.values[f, : local.counts[f]] for f in range(len(local.counts))],
        local_exact,
        local_mass,
        local_summaries or {},
    )
    gathered = host_allgather_objects(payload)
    if len(gathered) == 1:
        return local
    from .quantile_sketch import merge_summaries

    F = len(local.counts)
    per_feature: List[np.ndarray] = []
    for f in range(F):
        sets = [g[0][f] for g in gathered]
        exacts = [bool(g[1][f]) for g in gathered]
        masses = [float(g[2][f]) for g in gathered]
        union = np.unique(np.concatenate(sets))
        if discrete[f] or (all(exacts) and len(union) <= int(max_cnt_arr[f])):
            per_feature.append(union.astype(np.float32))
        elif all(f in g[3] for g in gathered):
            merged = gathered[0][3][f]
            for g in gathered[1:]:
                merged = merge_summaries(merged, g[3][f])
            per_feature.append(merged.query_values(int(max_cnt_arr[f])))
        else:
            per_feature.append(
                merge_quantile_candidates(sets, masses, int(max_cnt_arr[f]))
            )
    return _to_feature_bins(per_feature)


def build_bins_global(
    X: np.ndarray,
    weight: np.ndarray,
    params: GBDTParams,
    feature_names: Optional[Sequence[str]] = None,
    seed: int = 20170425,
) -> FeatureBins:
    """build_bins + multi-host candidate merge (no-op single-process)."""
    import jax

    local = build_bins(X, weight, params, feature_names, seed)
    if jax.process_count() == 1:
        return local
    from .quantile_sketch import Summary, WeightedQuantileSketch, prune_summary

    F = X.shape[1]
    names = feature_names or [str(i) for i in range(F)]
    exact = np.zeros((F,), bool)
    discrete = np.zeros((F,), bool)
    mass = np.zeros((F,), np.float64)
    max_cnt_arr = np.zeros((F,), np.int64)
    summaries: Dict[int, Summary] = {}
    for f in range(F):
        spec = _spec_for(f, names[f], params.approximate)
        max_cnt_arr[f] = spec.max_cnt
        if spec.type == "sample_by_quantile":
            # exact iff the sampler took the all-distinct path (tracked by
            # build_bins; candidate count alone misclassifies dedup'd picks)
            exact[f] = bool(local.exact[f]) if local.exact is not None else False
            w = (
                np.power(np.maximum(weight, 0.0), spec.alpha)
                if spec.use_sample_weight
                else np.ones_like(weight)
            )
            mass[f] = float(np.sum(w))
            # local GK summary for the bounded-error cross-process merge
            # (pruned to 4*max_cnt: rank error <= B/(8*max_cnt), an eighth
            # of the candidate spacing). Giant columns build one even when
            # locally exact — another host's shard may be inexact, and
            # without a summary on every host the merge would degrade to
            # the unbounded candidate-union fallback.
            b = max(4 * int(spec.max_cnt), 256)
            col = X[:, f]
            if len(col) > SKETCH_ROWS:
                sk = WeightedQuantileSketch(b=b)
                cs = 1 << 22
                for i in range(0, len(col), cs):
                    sk.push(col[i : i + cs], w[i : i + cs])
                summaries[f] = prune_summary(sk.summary(), b)
            else:
                # unconditional: a locally-exact shard still needs a summary
                # — another host's shard of the same column may be inexact,
                # and the bounded-error merge requires summaries on EVERY
                # host (exact Summaries are small and exact by construction)
                summaries[f] = prune_summary(Summary.from_exact(col, w), b)
        else:
            discrete[f] = True  # discrete samplers merge by set union
            exact[f] = True
            mass[f] = float(len(X))
    return merge_bins_multihost(
        local, exact, mass, max_cnt_arr, discrete, summaries
    )


# ---------------------------------------------------------------------------
# Serve-side bin-edge export: the trainer dumps each feature's sorted
# representative values next to the model (`<data_path>.bins.json`), so the
# serving layer can bin request rows ONCE per batch with the exact same
# nearest-representative rule the training matrix used (bin_matrix) and
# traverse the ensemble on small integer bin indices instead of float
# compares (serve/kernels.py, docs/serving.md "Precision rungs"). The
# sidecar rides the continual shadow/promote/archive moves (driver._roots)
# and the serving fingerprint (registry._sidecar_paths).
# ---------------------------------------------------------------------------

BIN_EDGES_SCHEMA = "ytk-bin-edges"


def bin_edges_path(data_path: str) -> str:
    return data_path + ".bins.json"


def model_text_digest(text: str) -> str:
    """sha256 of the dumped model text — pairs a bin-edges sidecar with
    the EXACT ensemble it was trained with (splits are midpoints, not
    edge members, so no per-value check can detect a mismatched grid)."""
    import hashlib

    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def dump_bin_edges(fs, path: str, names: Sequence[str], bins: FeatureBins,
                   split_type: str = "mean",
                   model_digest: Optional[str] = None) -> None:
    """Atomically dump per-feature representative values, name-keyed (the
    dumped trees are name-keyed too). Written BEFORE the model file so a
    fingerprint-watch reload never pairs a new ensemble with stale edges;
    `model_digest` (sha256 of the model text about to land) lets serving
    verify the pairing even across a crash between the two writes."""
    import json

    payload = {
        "schema": BIN_EDGES_SCHEMA,
        "version": 1,
        "split_type": split_type,
        "features": {
            str(names[f]): [
                float(v) for v in bins.values[f, : int(bins.counts[f])]
            ]
            for f in range(len(bins.counts))
        },
    }
    if model_digest is not None:
        payload["model_digest"] = model_digest
    with fs.atomic_open(path) as f:
        json.dump(payload, f)


def load_bin_edges(
    fs, path: str, model_digest: Optional[str] = None
) -> Optional[Dict[str, np.ndarray]]:
    """{feature name: sorted (cnt,) f64 edges} or None when the sidecar is
    missing/unreadable (serving then derives thresholds from the ensemble
    itself — serve/kernels.build_bin_table). When the caller passes the
    served model's text digest, a sidecar carrying a DIFFERENT digest is
    rejected — the new-edges/old-model window a crash between the trainer's
    two writes can leave behind would otherwise misroute interior rows."""
    import json
    import logging

    if not fs.exists(path):
        return None
    try:
        with fs.open(path) as f:
            payload = json.load(f)
        if payload.get("schema") != BIN_EDGES_SCHEMA:
            raise ValueError(f"not a bin-edges sidecar: {path}")
        want = payload.get("model_digest")
        if model_digest is not None and want is not None \
                and want != model_digest:
            logging.getLogger(__name__).warning(
                "bin-edges sidecar %s was dumped for a different model "
                "(digest mismatch); serving falls back to ensemble-derived "
                "thresholds", path,
            )
            return None
        return {
            str(name): np.asarray(vals, np.float64)
            for name, vals in payload["features"].items()
        }
    except (OSError, ValueError, KeyError, TypeError) as e:
        logging.getLogger(__name__).warning(
            "bin-edges sidecar %s unreadable (%s: %s); serving falls back "
            "to ensemble-derived thresholds", path, type(e).__name__, e,
        )
        return None


# ---------------------------------------------------------------------------
# Exclusive feature bundling (EFB, LightGBM §5): merge mutually-exclusive
# sparse columns into one offset-binned column at binning time, shrinking
# the bin matrix's feature axis before it ever reaches HBM.
# ---------------------------------------------------------------------------
#
# Bundle-column bin layout: bin 0 is the shared DEFAULT (every member at
# its zero value); member j's NONZERO bins 1..B_j-1 land at
# [lo_j, lo_j + B_j - 2] with lo offsets accumulating member widths.
# Candidates are restricted to columns with min >= 0 whose lowest
# representative is exactly 0, so "original bin 0" == "value 0" and the
# encoding is invertible. Conflict rows (two members nonzero) keep the
# higher-offset member's value — deterministic, and identical for train
# and test transforms. With conflict budget 0 the transform is lossless:
# the engine's range-corrected split enumeration (engine.split_kernel
# `ranges`) recovers exactly the per-original-feature splits, and
# `unbundle_split` maps a chosen (bundle, slot) back to the original
# feature id + bin interval, so dumped models and serving are unchanged.

#: candidate pre-filter: a column this dense can never bundle usefully
#: (and keeps the pairwise conflict matmul off dense features entirely)
EFB_MAX_DENSITY = 0.5
#: skip EFB planning past this many candidate columns (the conflict
#: matrix is O(C^2) memory)
EFB_MAX_CANDIDATES = 4096


@dataclass
class BundlePlan:
    """Column plan for an EFB-bundled bin matrix.

    Column layout: the unbundled original features first (in original
    order, `col_fid[c]` = original fid), then one column per bundle.
    `member_lo[b][k]`/`member_hi[b][k]` give member k's nonzero slot
    range inside bundle b's column."""

    n_features: int  # original F
    col_fid: np.ndarray  # (U,) i32: unbundled column -> original fid
    bundles: List[List[int]]  # each: >= 2 original fids, offset order
    member_lo: List[List[int]]
    member_hi: List[List[int]]

    @property
    def n_cols(self) -> int:
        return len(self.col_fid) + len(self.bundles)

    @property
    def n_bundled_features(self) -> int:
        return sum(len(m) for m in self.bundles)

    def bundle_width(self, b: int) -> int:
        return self.member_hi[b][-1] + 1

    def range_tables(self, B: int, F_pad: Optional[int] = None):
        """(range_lo, range_hi) (F_pad, B) int32 for engine.split_kernel:
        plain columns (and padding) get [0, B-1]; a bundle column's slot s
        gets the member range containing s. Slots outside any member
        range (bin 0, tail padding) keep [0, B-1] — they are never valid
        split boundaries (bin 0 has no predecessor; tail slots are
        empty), so the value only has to be harmless."""
        F_pad = F_pad or self.n_cols
        rlo = np.zeros((F_pad, B), np.int32)
        rhi = np.full((F_pad, B), B - 1, np.int32)
        U = len(self.col_fid)
        for b in range(len(self.bundles)):
            for lo, hi in zip(self.member_lo[b], self.member_hi[b]):
                rlo[U + b, lo : hi + 1] = lo
                rhi[U + b, lo : hi + 1] = hi
        return rlo, rhi

    def member_of_slot(self, col: int, slot: int):
        """(original fid, member lo) of the member whose nonzero range
        contains `slot` in bundle column `col`."""
        b = col - len(self.col_fid)
        for fid, lo, hi in zip(
            self.bundles[b], self.member_lo[b], self.member_hi[b]
        ):
            if lo <= slot <= hi:
                return fid, lo
        raise ValueError(
            f"slot {slot} of bundle column {col} is in no member range"
        )

    def unbundle_split(self, col: int, slot_l: int, slot_r: int):
        """Map a chosen split (column, boundary interval [slot_l, slot_r])
        back to (original fid, original slot_l, original slot_r).

        Plain columns pass through. For a bundle, the boundary slot_r
        identifies the member; bundle slot s maps to original bin
        s - lo + 1 (member nonzero bins start at original bin 1), and a
        slot_l below the member's range (the lo-1 default encoding from
        split_kernel, or bin 0) maps to the original zero bin 0."""
        U = len(self.col_fid)
        if col < U:
            return int(self.col_fid[col]), slot_l, slot_r
        fid, lo = self.member_of_slot(col, slot_r)
        orig_r = slot_r - lo + 1
        orig_l = 0 if slot_l < lo else slot_l - lo + 1
        return fid, orig_l, orig_r

    def summary(self) -> str:
        sizes = ",".join(str(len(m)) for m in self.bundles)
        return (
            f"{self.n_bundled_features} of {self.n_features} features in "
            f"{len(self.bundles)} bundle(s) [{sizes}]: "
            f"{self.n_features} -> {self.n_cols} columns"
        )


def efb_candidates(
    nnz: np.ndarray,
    mins: np.ndarray,
    bins: FeatureBins,
    n_rows: int,
    max_density: float = EFB_MAX_DENSITY,
) -> np.ndarray:
    """Original fids eligible for bundling: sparse (nnz fraction under the
    density cap), non-negative, at least one nonzero bin, and binned so
    that value 0 IS bin 0 (lowest representative exactly 0 — the offset
    encoding's invertibility condition)."""
    F = len(nnz)
    out = []
    for f in range(F):
        cnt = int(bins.counts[f])
        if (
            cnt >= 2
            and nnz[f] > 0
            and nnz[f] <= max_density * n_rows
            and mins[f] >= 0
            and float(bins.values[f, 0]) == 0.0
        ):
            out.append(f)
    return np.asarray(out, np.int64)


def plan_bundles(
    cand: np.ndarray,
    conflicts: np.ndarray,
    bin_counts: np.ndarray,
    F: int,
    max_conflict: int,
    max_width: int,
) -> Optional[BundlePlan]:
    """Greedy graph-coloring over the candidate conflict counts
    (LightGBM Alg. 3): visit candidates by nonzero count (conflict-matrix
    diagonal) descending, place each into the first bundle whose total
    conflict stays within `max_conflict` and whose width (1 shared
    default bin + each member's nonzero bins) fits `max_width`. Bundles
    that end up with one member stay unbundled. Returns None when nothing
    bundles (the caller's no-op path)."""
    if len(cand) < 2:
        return None
    nnz = np.diag(conflicts)
    order = np.argsort(-nnz, kind="stable")  # dense-first, fid tie-break
    groups: List[List[int]] = []  # candidate-local indices
    g_conf: List[int] = []
    g_width: List[int] = []
    for ci in order:
        w = int(bin_counts[cand[ci]]) - 1  # nonzero bins
        placed = False
        for gi, members in enumerate(groups):
            add = int(sum(conflicts[ci, m] for m in members))
            if g_conf[gi] + add <= max_conflict and g_width[gi] + w <= max_width:
                members.append(int(ci))
                g_conf[gi] += add
                g_width[gi] += w
                placed = True
                break
        if not placed:
            groups.append([int(ci)])
            g_conf.append(0)
            g_width.append(1 + w)
    bundles = [
        sorted(int(cand[m]) for m in members)
        for members in groups
        if len(members) >= 2
    ]
    if not bundles:
        return None
    bundles.sort()  # deterministic column order by smallest member fid
    bundled = set()
    for members in bundles:
        bundled.update(members)
    col_fid = np.asarray(
        [f for f in range(F) if f not in bundled], np.int32
    )
    member_lo: List[List[int]] = []
    member_hi: List[List[int]] = []
    for members in bundles:
        lo_list, hi_list = [], []
        off = 1  # bin 0 = shared default
        for fid in members:
            w = int(bin_counts[fid]) - 1
            lo_list.append(off)
            hi_list.append(off + w - 1)
            off += w
        member_lo.append(lo_list)
        member_hi.append(hi_list)
    return BundlePlan(
        n_features=F,
        col_fid=col_fid,
        bundles=bundles,
        member_lo=member_lo,
        member_hi=member_hi,
    )


def build_bundle_plan(
    X_t,
    bins: FeatureBins,
    max_conflict: int,
    max_width: int,
    nnz: Optional[np.ndarray] = None,
    mins: Optional[np.ndarray] = None,
) -> Optional[BundlePlan]:
    """Plan EFB bundles from a transposed (F, n) matrix (device jnp array,
    host numpy or a ColumnsT — the nonzero-pattern reductions and the
    candidate conflict matmul run wherever the matrix lives). Host callers can pass
    precomputed (nnz, mins) from gbdt.data.column_stats to keep the
    full-matrix boolean pattern from materializing. Returns None when
    nothing bundles."""
    import jax.numpy as jnp

    cols = X_t if isinstance(X_t, ColumnsT) else None
    if cols is not None and cols.whole is not None:
        X_t, cols = cols.whole, None
    is_dev = not isinstance(X_t, np.ndarray)
    xp = jnp if is_dev else np
    if cols is not None:
        # no transposed copy of the matrix: the column statistics a range
        # of columns at a time, the candidates' rows gathered below
        F, n = cols.F, cols.n
        stats = [
            (np.asarray(jnp.sum(part != 0, axis=1)),
             np.asarray(jnp.min(part, axis=1)))
            for _, _, part in cols.chunks()
        ]
        nnz = np.concatenate([s[0] for s in stats]).astype(np.int64)
        mins = np.concatenate([s[1] for s in stats])
    else:
        F, n = X_t.shape
    if nnz is None:
        nnz = np.asarray(xp.sum(X_t != 0, axis=1)).astype(np.int64)
    if mins is None:
        mins = np.asarray(xp.min(X_t, axis=1))
    cand = efb_candidates(nnz, mins, bins, n)
    if len(cand) < 2:
        return None
    C = len(cand)
    if C > EFB_MAX_CANDIDATES:
        return None  # O(C^2) conflict matrix would blow memory; skip
    # exact pairwise co-nonzero counts, chunked over rows so the (C, chunk)
    # f32 nonzero pattern stays within a fixed memory budget on either
    # backend (budget 0 MUST see every conflict — a sampled estimate could
    # silently bundle conflicting features)
    if cols is not None:
        Xc = jnp.transpose(cols.X[:, jnp.asarray(cand)])
    else:
        Xc = X_t[xp.asarray(cand)] if is_dev else X_t[np.asarray(cand)]
    # chunk cap 2^22 keeps per-chunk counts exactly representable in f32
    chunk = min(1 << 22, max(8192, (1 << 26) // max(C, 1)))
    conflicts = np.zeros((C, C), np.float64)
    for i in range(0, n, chunk):
        Zc = (Xc[:, i : i + chunk] != 0).astype(xp.float32)
        conflicts += np.asarray(Zc @ Zc.T, np.float64)
    conflicts = np.rint(conflicts).astype(np.int64)  # [i,j] = co-nonzero rows
    return plan_bundles(
        cand, conflicts, bins.counts, F, max_conflict, max_width
    )


def bundle_bin_matrix_t(bins_t, plan: BundlePlan):
    """Apply a BundlePlan to a transposed (F, n) BIN matrix -> (n_cols, n).

    Works on device (jnp) and host (np) arrays alike. Bundle encoding per
    row: member j nonzero (orig bin > 0) -> lo_j + bin_j - 1, all-default
    -> 0; the elementwise max picks the highest-offset member on conflict
    rows (the budgeted-conflict winner rule)."""
    import jax.numpy as jnp

    xp = np if isinstance(bins_t, np.ndarray) else jnp
    parts = [bins_t[np.asarray(plan.col_fid)]] if len(plan.col_fid) else []
    for b, members in enumerate(plan.bundles):
        acc = None
        for fid, lo in zip(members, plan.member_lo[b]):
            bf = bins_t[fid].astype(xp.int32)
            enc = xp.where(bf > 0, lo + bf - 1, 0)
            acc = enc if acc is None else xp.maximum(acc, enc)
        parts.append(acc[None].astype(bins_t.dtype))
    return xp.concatenate(parts, axis=0)


def quantile_bins_device(
    X_t,
    weight: Optional[np.ndarray],
    spec: ApproximateSpec,
) -> Tuple[np.ndarray, np.ndarray]:
    """sample_by_quantile on device: one sort per feature on the TPU instead
    of the host argsort/cumsum path of `_sample_feature` (which costs ~4s per
    feature at 10M rows). Same selection rule: candidates at max_cnt evenly
    spaced weighted ranks of the sorted column; features whose distinct count
    fits max_cnt keep every distinct value (reference:
    SampleByQuantile.java:60-105 — sketch query at even ranks).

    X_t: (F, n) device array, or a ColumnsT (a range of columns a sort;
    columns are sorted independently, so the parts are the whole's rows).
    Returns (candidates (F, max_cnt) f32 with possible duplicates,
    distinct_counts (F,) int) on host; the caller dedupes/finalizes per
    feature.
    """
    import jax
    import jax.numpy as jnp

    if isinstance(X_t, ColumnsT):
        n, parts = X_t.n, (part for _, _, part in X_t.chunks())
    else:
        n, parts = X_t.shape[1], (X_t,)
    mc = spec.max_cnt
    uniform = weight is None or (
        spec.alpha == 0.0
        or not spec.use_sample_weight
        or (np.min(weight) == np.max(weight))
    )
    ranks = jnp.asarray(np.arange(1, mc + 1) / mc, jnp.float32)
    # uniform weights: cw[i] = i+1 -> pos = ceil(rank*n) - 1, computed in
    # float64 on host (f32 loses integer precision above ~16M rows)
    pos_uniform = jnp.asarray(
        np.clip(np.ceil(np.arange(1, mc + 1) / mc * n).astype(np.int64) - 1, 0, n - 1),
        jnp.int32,
    )

    @jax.jit
    def run_uniform(X_t):
        sv = jnp.sort(X_t, axis=1)
        distinct = jnp.sum(sv[:, 1:] != sv[:, :-1], axis=1) + 1
        return sv[:, pos_uniform], distinct

    @jax.jit
    def run_weighted(X_t, w):
        ops = jax.vmap(lambda col: jax.lax.sort((col, w), num_keys=1))(X_t)
        sv, sw = ops
        cw = jnp.cumsum(sw.astype(jnp.float32), axis=1)
        total = cw[:, -1:]
        tgt = ranks[None, :] * total  # (F, mc)
        # first i with cw[i] >= tgt  == count of cw[i] < tgt
        pos = jax.vmap(lambda c, t: jnp.searchsorted(c, t, side="left"))(cw, tgt)
        pos = jnp.clip(pos, 0, n - 1)
        cand = jnp.take_along_axis(sv, pos, axis=1)
        distinct = jnp.sum(sv[:, 1:] != sv[:, :-1], axis=1) + 1
        return cand, distinct

    if uniform:
        run = run_uniform
    else:
        w_pow = jnp.asarray(
            np.power(np.maximum(weight, 0.0), spec.alpha).astype(np.float32)
        )

        def run(part):
            return run_weighted(part, w_pow)

    # a part's candidates are on the host before the next part is sorted
    out = [tuple(np.asarray(a) for a in run(part)) for part in parts]
    return (np.concatenate([c for c, _ in out]),
            np.concatenate([d for _, d in out]))


def build_bins_maybe_device(
    X: np.ndarray,
    X_t_dev,
    weight: np.ndarray,
    params: GBDTParams,
    feature_names: Optional[Sequence[str]] = None,
    seed: int = 20170425,
) -> FeatureBins:
    """build_bins, offloading the quantile sampler to the device when every
    feature uses one sample_by_quantile spec (the common/acceptance config).
    Falls back to the host path per feature otherwise, and for features
    whose distinct count fits max_cnt (those keep all distinct values)."""
    specs = params.approximate
    single_quantile = (
        X_t_dev is not None
        and len(specs) == 1
        and specs[0].type == "sample_by_quantile"
    )
    if not single_quantile:
        return build_bins(X, weight, params, feature_names, seed)
    spec = specs[0]
    cand, distinct = quantile_bins_device(X_t_dev, weight, spec)
    F = X.shape[1]
    per_feature: List[np.ndarray] = []
    for f in range(F):
        if distinct[f] <= spec.max_cnt:
            vals = np.unique(X[:, f])  # small-cardinality feature: keep all
        else:
            vals = np.unique(cand[f])
        if len(vals) == 0:
            vals = np.zeros((1,), np.float32)
        per_feature.append(np.sort(vals).astype(np.float32))
    return _to_feature_bins(per_feature)


_BIN_IDS = None  # the jitted value->bin program, made at first use


def _bin_ids():
    """(X_t (F, n), values (F, B), counts (F,)) -> (F, n) int32 bin ids."""
    global _BIN_IDS
    if _BIN_IDS is None:
        import jax
        import jax.numpy as jnp

        def per_feature(col, v, cnt):
            last = v[cnt - 1]
            # first index with v[i] >= col == count of v[i] < col
            i = jnp.sum(v[None, :] < col[:, None], axis=1).astype(jnp.int32)
            # NaN (unfilled missing) -> last bin, matching host np.searchsorted
            # which sorts NaN above everything
            over = (col > last) | jnp.isnan(col)
            i = jnp.clip(i, 0, cnt - 1)
            prev = v[jnp.maximum(i - 1, 0)]
            mids = 0.5 * (prev + v[i])
            i = jnp.where((i >= 1) & (col < mids) & ~over, i - 1, i)
            return jnp.where(over, cnt - 1, i)

        _BIN_IDS = jax.jit(jax.vmap(per_feature))
    return _BIN_IDS


def bin_matrix_device(X_t_dev, bins: FeatureBins, n_pad: int = None,
                      dtype=None):
    """Device-side value->bin conversion into the transposed (F, n) layout
    the growth engine wants (same rule as `bin_matrix`; the compare-count
    searchsorted fuses on TPU instead of a 28-feature host loop).

    X_t_dev: (F, n) device array -> (F, n) int32 bin ids; or a ColumnsT,
    binned a range of columns at a time, each range padded with zero rows
    to `n_pad` and narrowed to `dtype` before the next is read, so that no
    int32 matrix of all columns exists -> (F, n_pad) `dtype`."""
    import jax
    import jax.numpy as jnp

    run = _bin_ids()
    if not isinstance(X_t_dev, ColumnsT):
        return run(X_t_dev, jnp.asarray(bins.values), jnp.asarray(bins.counts))
    cols = X_t_dev
    parts = []
    for lo, hi, part in cols.chunks():
        ids = run(
            jnp.pad(part, ((0, 0), (0, n_pad - cols.n))),
            jnp.asarray(bins.values[lo:hi]), jnp.asarray(bins.counts[lo:hi]),
        ).astype(dtype)
        if cols.whole is None:
            # the host runs ahead of the device: unsettled, every range's
            # transposed, padded and int32 copies would be allocated before
            # the first is freed (seen as a peak that differed by a range's
            # bytes from run to run: my chip runs, PR 39)
            jax.block_until_ready(ids)
        parts.append(ids)
        del part, ids
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def bin_matrix(X: np.ndarray, bins: FeatureBins) -> np.ndarray:
    """Raw values -> nearest-representative bin ids, vectorized
    (reference: FeatureApprData.convertFeaVal2ApprFeaIndex:179).

    rule: i = first index with values[i] >= v (v > max -> last bin);
          if i >= 1 and v < midpoint(values[i-1], values[i]) -> i-1
    i.e. round to the nearest representative, ties to the upper one."""
    n, F = X.shape
    out = np.empty((n, F), np.int32)
    for f in range(F):
        cnt = int(bins.counts[f])
        v = bins.values[f, :cnt]
        if cnt == 1:
            out[:, f] = 0
            continue
        col = X[:, f]
        i = np.searchsorted(v, col, side="left")  # ceil index
        over = col > v[-1]
        i = np.clip(i, 0, cnt - 1)
        mids = 0.5 * (v[np.maximum(i - 1, 0)] + v[i])
        i = np.where((i >= 1) & (col < mids) & ~over, i - 1, i)
        out[:, f] = np.where(over, cnt - 1, i)
    return out
