"""Data ingest — the TPU rebuild of the reference DataFlow/CoreData load path.

The reference parses text lines into per-thread blocked-CSR int arrays
(reference: dataflow/CoreData.java:536-645, dataflow/DataFlow.java:468-765).
Here the terminal format is *padded ELL* arrays — `(n, width)` feature-index
and value matrices — because static shapes are what XLA wants: Xv becomes a
gather+reduce, XTv a segment-sum, both jit-able with no ragged rows.

Pipeline (mirrors DataFlow.loadFlow):
    lines -> (py transform hook) -> parse (weight###label###f:v,...)
          -> y-sampling / error tolerance
          -> feature count map + transform stats        [train only]
          -> feature dict build (sorted names) or load  [train only]
          -> transform value rewrite
          -> ELL arrays (bias at index 0 when need_bias)
"""

from __future__ import annotations

import dataclasses
import math
import random
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..config.params import CommonParams, DelimParams
from ..obs import (
    health,
    heartbeat as obs_heartbeat,
    inc as obs_inc,
    span as obs_span,
)
from .feature_hash import FeatureHash
from .fs import FileSystem, LocalFileSystem


# ---------------------------------------------------------------------------
# Line parsing
# ---------------------------------------------------------------------------


@dataclass
class ParsedLine:
    weight: float
    labels: List[float]  # 1 entry for scalar losses; K for multiclass
    feats: List[Tuple[str, float]]


def parse_line(line: str, delim: DelimParams) -> ParsedLine:
    """`weight###label[,label...]###name:val,name:val` (reference:
    CoreData.trainDataSplit/weightExtract/yExtract/line2FeatureMap)."""
    info = line.strip().split(delim.x_delim)
    weight = float(info[0])
    labels = [float(v) for v in info[1].split(delim.y_delim)]
    feats: List[Tuple[str, float]] = []
    ftext = info[2].strip()
    if ftext:
        for f in ftext.split(delim.features_delim):
            name, _, val = f.partition(delim.feature_name_val_delim)
            feats.append((name.strip(), float(val)))
    return ParsedLine(weight, labels, feats)


def load_transform_hook(path: str) -> Callable[[bytes], List[str]]:
    """Load the user data-transform hook: a python file defining
    `transform(line: bytes) -> list[str]`. The reference embeds Jython for
    this (reference: dataflow/DataUtils.java:142, bin/transform.py); here it
    is plain Python."""
    ns: Dict = {}
    with LocalFileSystem().open(path) as f:
        exec(compile(f.read(), path, "exec"), ns)
    if "transform" not in ns:
        raise ValueError(f"{path} does not define transform(bytearray) -> [lines]")
    return ns["transform"]


# ---------------------------------------------------------------------------
# Feature statistics / transform
# ---------------------------------------------------------------------------


@dataclass
class FeatureStat:
    """Running (cnt, sum, sum2, min, max) (reference: CoreData.FeatureStat:107)."""

    cnt: int = 0
    sum: float = 0.0
    sum2: float = 0.0
    max: float = -math.inf
    min: float = math.inf

    def update(self, v: float) -> None:
        self.cnt += 1
        self.sum += v
        self.sum2 += v * v
        if v > self.max:
            self.max = v
        if v < self.min:
            self.min = v

    def merge(self, o: "FeatureStat") -> None:
        self.cnt += o.cnt
        self.sum += o.sum
        self.sum2 += o.sum2
        self.max = max(self.max, o.max)
        self.min = min(self.min, o.min)


@dataclass
class TransformNode:
    """Standardization / range-scaling of one feature
    (reference: CoreData.TransformNode:155; sidecar text format kept
    byte-compatible so reference predictors can read it)."""

    mode: str  # standardization | scale_range
    mean: float = 0.0
    stdvar: float = 0.0
    max: float = 0.0
    min: float = 0.0
    range_max: float = 1.0
    range_min: float = -1.0

    def transform(self, val: float) -> float:
        if self.mode == "standardization":
            if self.stdvar < 1e-6:
                return val
            return (val - self.mean) / self.stdvar
        if abs(self.max - self.min) < 1e-6:
            return 1.0
        return self.range_min + (self.range_max - self.range_min) * (
            (val - self.min) / (self.max - self.min)
        )

    def __str__(self) -> str:  # sidecar line payload
        return (
            f"mode={self.mode}, mean={self.mean}, stdvar={self.stdvar}, "
            f"max={self.max}, min={self.min}, rangeMax={self.range_max}, "
            f"rangeMin={self.range_min}"
        )

    @classmethod
    def from_string(cls, s: str) -> "TransformNode":
        info = [kv.split("=")[1].strip() for kv in s.split(",")]
        return cls(
            mode=info[0].lower(),
            mean=float(info[1]),
            stdvar=float(info[2]),
            max=float(info[3]),
            min=float(info[4]),
            range_max=float(info[5]),
            range_min=float(info[6]),
        )

    @classmethod
    def from_stat(
        cls, stat: FeatureStat, mode: str, range_max: float, range_min: float
    ) -> "TransformNode":
        mean = stat.sum / stat.cnt
        mean2 = stat.sum2 / stat.cnt
        return cls(
            mode=mode,
            mean=mean,
            stdvar=math.sqrt(max(mean2 - mean * mean, 0.0)),
            max=stat.max,
            min=stat.min,
            range_max=range_max,
            range_min=range_min,
        )


# ---------------------------------------------------------------------------
# The dataset container
# ---------------------------------------------------------------------------


@dataclass
class SparseDataset:
    """Padded ELL sparse rows, host side (numpy), jit-ready.

    idx[i, j] / val[i, j] hold the j-th (feature, value) of row i; padding
    entries have idx=0, val=0.0 (harmless: they add 0·w[0] to scores and 0 to
    grads). When need_bias, every row's first slot is (0, 1.0) — index 0 *is*
    the bias feature, as in the reference dict layout
    (reference: DataFlow.reduceFeature fName2IndexMap bias at 0).
    """

    idx: np.ndarray  # (n, width) int32
    val: np.ndarray  # (n, width) float32
    y: np.ndarray  # (n,) or (n, K) float32
    weight: np.ndarray  # (n,) float32
    n_real: int  # rows before padding
    dim: int  # feature dimension (dict size)
    field: Optional[np.ndarray] = None  # (n, width) int32, FFM only

    @property
    def n(self) -> int:
        return self.idx.shape[0]

    def pad_rows(self, multiple: int) -> "SparseDataset":
        """Pad row count to a multiple (mesh divisibility). Padding rows have
        weight 0 so every weighted reduction ignores them — the static-shape
        replacement for the reference's ragged per-worker row counts."""
        n = self.idx.shape[0]
        target = (n + multiple - 1) // multiple * multiple
        return self.pad_rows_to(target)

    def pad_rows_to(self, target: int) -> "SparseDataset":
        """Pad to an exact row count (multi-process shard equalization —
        an empty shard still pads up to the group-agreed target)."""
        n = self.idx.shape[0]
        if target <= n:
            return self
        pad = target - n

        def rows(a):
            # rows handed over on the device (a benchmark's, a caller's) stay there
            if isinstance(a, np.ndarray):
                xp = np
            else:
                import jax.numpy as xp
            return xp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

        return dataclasses.replace(
            self,
            idx=rows(self.idx),
            val=rows(self.val),
            y=rows(self.y),
            weight=rows(self.weight),
            field=None if self.field is None else rows(self.field),
        )


def _slot_id_range(xp, idx, val):
    """Per slot, the least and the largest id among its non-zero entries
    (int32 max and -1 where it has none)."""
    live = val != 0
    lo = xp.min(xp.where(live, idx, np.iinfo(np.int32).max), axis=0)
    hi = xp.max(xp.where(live, idx, -1), axis=0)
    return lo, hi


def constant_slots(idx, val) -> np.ndarray:
    """Which id does slot j hold in every row? -> (width,) int32, -1 where
    the slot's id differs between rows.

    Slot j is constant with id c iff every row has `idx[r, j] == c` or
    `val[r, j] == 0`: a zero-valued entry (a padded-ELL pad `(0, 0.0)`, a
    zero-weight pad row) adds exactly 0 to any score and any gradient
    whatever its id, so it decides nothing. A slot whose values are all
    zero is constant with any id and reads 0. Two reductions over the rows:
    numpy rows are read on the host, device rows (row-sharded ones too) by
    one jitted program where they lie."""
    if isinstance(idx, np.ndarray) and isinstance(val, np.ndarray):
        lo, hi = _slot_id_range(np, idx, val)
    else:
        import jax
        import jax.numpy as jnp

        lo, hi = jax.jit(_slot_id_range, static_argnums=0)(jnp, idx, val)
        lo, hi = np.asarray(lo), np.asarray(hi)
    ids = np.where(lo == hi, hi, -1)
    return np.where(hi < 0, 0, ids).astype(np.int32)


@dataclass
class _Cols:
    """Columnar rows from the native parser (post label-expansion, hashing,
    y-sampling): the fast-path replacement for List[ParsedLine]."""

    weight: np.ndarray  # (n,) f32
    y: np.ndarray  # (n,) or (n, K) f32
    occ_row: np.ndarray  # (nnz,) i64 row of each feature occurrence
    occ_name: np.ndarray  # (nnz,) i64 -> names
    occ_val: np.ndarray  # (nnz,) f64
    names: List[str]


def _counts_from_rows(rows: Sequence[ParsedLine]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for r in rows:
        for name, _ in r.feats:
            counts[name] = counts.get(name, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# The ingest driver (DataFlow equivalent)
# ---------------------------------------------------------------------------


@dataclass
class IngestResult:
    train: SparseDataset
    test: Optional[SparseDataset]
    feature_map: Dict[str, int]  # name -> global index
    transform_nodes: Dict[int, TransformNode] = field(default_factory=dict)
    # global label stats (reference: CoreData.globalSync y stats)
    y_real_stat: Optional[np.ndarray] = None
    y_weight_stat: Optional[np.ndarray] = None


def shard_plan(fs, data_params, paths) -> Tuple[Sequence[str], int, int]:
    """This process's read plan: (paths, divisor, remainder). The single
    source of truth for the assigned / files_avg / lines_avg dispatch
    (reference: DataFlow.java:391-410) — shared by the python line reader
    and the native parser so both always read the same shard."""
    import jax

    n_proc = jax.process_count()
    proc = jax.process_index()
    if data_params.assigned or n_proc == 1:
        return paths, 1, 0
    if data_params.unassigned_mode == "files_avg":
        files = sorted(fs.recur_get_paths(paths))
        return files[proc::n_proc], 1, 0
    return paths, n_proc, proc


def shard_read_lines(fs, data_params, paths):
    """This process's line shard (assigned mode reads everything; unassigned
    splits by files_avg or line-modulo lines_avg across processes)."""
    paths, divisor, remainder = shard_plan(fs, data_params, paths)
    if divisor == 1:
        return fs.read_lines(paths)
    return fs.select_read_lines(paths, divisor, remainder)


class DataIngest:
    """Single-host ingest (the TPU host driver replaces per-thread CoreData
    shards: one process parses, the mesh shards rows on device). Multi-host
    processes each parse their line-modulo shard and merge dict/stats via
    host collectives (parallel.collectives.host_allgather_objects)."""

    def __init__(
        self,
        params: CommonParams,
        fs: Optional[FileSystem] = None,
        n_labels: int = 1,
        label_as_class_index: bool = False,
        transform_hook: Optional[Callable[[bytes], List[str]]] = None,
        field_map: Optional[Dict[str, int]] = None,
    ):
        self.params = params
        self.fs = fs or LocalFileSystem()
        self.n_labels = n_labels  # K for multiclass losses, else 1
        self.label_as_class_index = label_as_class_index
        self.transform_hook = transform_hook
        # FFM: field = feature-name prefix before field_delim, mapped through
        # the field dict; features with unknown fields are dropped
        # (reference: FFMModelDataFlow.updateX)
        self.field_map = field_map
        p = params
        self.hash = (
            FeatureHash(
                p.feature.feature_hash.bucket_size,
                p.feature.feature_hash.seed,
                p.feature.feature_hash.feature_prefix,
            )
            if p.feature.feature_hash.need_feature_hash
            else None
        )
        self.rng = random.Random(20170425)

    # -- parsing --------------------------------------------------------

    def _expand_labels(self, labels: List[float], line: str) -> List[float]:
        K = self.n_labels
        if K == 1:
            return labels[:1]
        if len(labels) == K:
            return labels
        if len(labels) == 1:
            clazz = int(labels[0])
            if clazz >= K:
                raise ValueError(f"label must be in [0,{K-1}]: {line}")
            out = [0.0] * K
            out[clazz] = 1.0
            return out
        raise ValueError(f"label num must be {K} or 1: {line}")

    def parse_rows(
        self, lines: Iterable[str], max_error_tol: int, is_train: bool
    ) -> List[ParsedLine]:
        delim = self.params.data.delim
        ys = dict(self.params.data.y_sampling)
        rows: List[ParsedLine] = []
        errors = 0
        subsampled = 0  # parse-valid lines dropped by y_sampling
        hb = obs_heartbeat("ingest.parse", every_s=30.0)
        for raw in lines:
            if len(rows) & 0xFFFF == 0 and rows:
                hb.beat(rows=len(rows), errors=errors)
            if not raw.strip():
                continue
            for line in (
                self.transform_hook(raw.encode("utf-8")) if self.transform_hook else [raw]
            ):
                try:
                    pl = parse_line(line, delim)
                    pl.labels = self._expand_labels(pl.labels, line)
                    if self.hash is not None:
                        pl.feats = self.hash.hash_features(pl.feats)
                    if is_train and ys:
                        # label-dependent subsampling with inverse-probability
                        # weight correction (reference: CoreData.yExtract) —
                        # inside the try so a label vector without an exact
                        # 1.0 counts toward max_error_tol like any bad line
                        label_idx = (
                            pl.labels.index(1.0)
                            if len(pl.labels) > 1
                            else int(pl.labels[0])
                        )
                        rate = ys.get(str(label_idx))
                        if rate is not None:
                            pl.weight *= (1.0 / rate) if rate <= 1.0 else rate
                            if self.rng.random() > rate:
                                subsampled += 1
                                continue
                except Exception:
                    errors += 1
                    if errors > max_error_tol:
                        raise
                    continue
                rows.append(pl)
        obs_inc("ingest.rows_parsed", len(rows))
        obs_inc("ingest.error_lines", errors)
        # rate sentinel under the absolute max_error_tol cap: a feed that is
        # mostly garbage but below the cap should still raise a flag. The
        # denominator counts parse-valid lines BEFORE y_sampling drops so
        # heavy subsampling can't inflate the rate.
        health.check_ingest(
            "ingest.parse", errors, len(rows) + subsampled, is_train=is_train
        )
        return rows

    # -- dict -----------------------------------------------------------

    def build_feature_map(self, rows: Sequence[ParsedLine]) -> Dict[str, int]:
        """Count -> filter(threshold) -> sorted names -> indices, bias at 0
        (reference: DataFlow.reduceFeature:294)."""
        return self.finalize_feature_map(_counts_from_rows(rows))

    def finalize_feature_map(self, counts: Dict[str, int]) -> Dict[str, int]:
        """Shared dict finalization: cross-process count merge, threshold
        filter, sorted names, bias at 0."""
        p = self.params
        counts = self._merge_counts(counts)
        thr = p.feature.filter_threshold
        names = sorted(n for n, c in counts.items() if c >= thr)
        fmap: Dict[str, int] = {}
        delta = 0
        if p.model.need_bias:
            fmap[p.model.bias_feature_name] = 0
            delta = 1
            if p.model.bias_feature_name in names:
                names.remove(p.model.bias_feature_name)
        for i, n in enumerate(names):
            fmap[n] = i + delta
        return fmap

    def _merge_counts(self, counts: Dict[str, int]) -> Dict[str, int]:
        """Across processes (multi-host): union-sum the count maps — the
        allreduceMap equivalent (reference: CoreData.globalSync:628)."""
        from ..parallel.collectives import host_allgather_objects

        all_counts = host_allgather_objects(counts)
        if len(all_counts) == 1:
            return counts
        merged: Dict[str, int] = {}
        for c in all_counts:
            for k, v in c.items():
                merged[k] = merged.get(k, 0) + v
        return merged

    def load_feature_map(self, dict_paths: Sequence[str]) -> Dict[str, int]:
        """reference: DataFlow.loadDict:244 — bias at 0, then dict file lines
        in sorted-path order. Rank0 reads, peers take its broadcast — dict
        sidecars are rank0-only dumps, so on non-shared storage other ranks
        must not read (or miss) a divergent copy (ADVICE r3)."""
        from ..parallel.collectives import load_on_rank0

        def read_names():
            out: List[str] = []
            for path in sorted(self.fs.recur_get_paths(dict_paths)):
                with self.fs.open(path) as f:
                    out.extend(line.strip() for line in f)
            return out

        names = load_on_rank0(read_names)
        p = self.params
        fmap: Dict[str, int] = {}
        if p.model.need_bias:
            fmap[p.model.bias_feature_name] = 0
        for name in names:
            if name and name not in fmap:
                fmap[name] = len(fmap)
        return fmap

    # -- transform ------------------------------------------------------

    def compute_transform_nodes(
        self, rows: Sequence[ParsedLine], fmap: Dict[str, int]
    ) -> Dict[int, TransformNode]:
        if not self.params.feature.transform.switch_on:
            return {}
        stats: Dict[str, FeatureStat] = {}
        for r in rows:
            for name, v in r.feats:
                s = stats.get(name)
                if s is None:
                    stats[name] = s = FeatureStat()
                s.update(v)
        return self.nodes_from_stats(stats, fmap)

    def nodes_from_stats(
        self, stats: Dict[str, FeatureStat], fmap: Dict[str, int]
    ) -> Dict[int, TransformNode]:
        """Cross-process stat merge + include/exclude selection -> nodes."""
        p = self.params
        t = p.feature.transform
        # multi-host merge
        from ..parallel.collectives import host_allgather_objects

        all_stats = host_allgather_objects(stats)
        if len(all_stats) > 1:
            merged: Dict[str, FeatureStat] = {}
            for st in all_stats:
                for k, v in st.items():
                    if k in merged:
                        merged[k].merge(v)
                    else:
                        merged[k] = dataclasses.replace(v)
            stats = merged

        include, exclude = set(t.include_features), set(t.exclude_features)
        names = set(fmap) - {p.model.bias_feature_name}
        chosen = include or (names - exclude if exclude else names)
        nodes: Dict[int, TransformNode] = {}
        for name in chosen:
            if name in stats and name in fmap:
                nodes[fmap[name]] = TransformNode.from_stat(
                    stats[name], t.mode, t.scale_max, t.scale_min
                )
        return nodes

    def write_transform_sidecar(
        self, nodes: Dict[int, TransformNode], fmap: Dict[str, int]
    ) -> None:
        """`<model>_feature_transform_stat` sidecar, reference text format
        (reference: DataFlow.reduceFeature stat writer, FEATURE_TRANSFORM_STAT)."""
        if not nodes:
            return
        inv = {i: n for n, i in fmap.items()}
        path = self.params.model.data_path + "_feature_transform_stat"
        with self.fs.atomic_open(path) as f:
            for i, node in sorted(nodes.items()):
                f.write(f"{inv[i]}###{node}\n")

    def load_transform_sidecar(self, fmap: Dict[str, int]) -> Dict[int, TransformNode]:
        path = self.params.model.data_path + "_feature_transform_stat"
        nodes: Dict[int, TransformNode] = {}
        if not self.fs.exists(path):
            return nodes
        from ..transform.sidecar import read_sidecar

        named, _digest = read_sidecar(self.fs, path)  # '#' header skipped
        for name, node in named.items():
            if name in fmap:
                nodes[fmap[name]] = node
        return nodes

    # -- materialization -------------------------------------------------

    def to_dataset(
        self,
        rows: Sequence[ParsedLine],
        fmap: Dict[str, int],
        nodes: Optional[Dict[int, TransformNode]] = None,
    ) -> SparseDataset:
        p = self.params
        nodes = nodes or {}
        need_bias = p.model.need_bias
        n = len(rows)
        K = self.n_labels
        fm = self.field_map
        fdelim = p.data.delim.field_delim
        mapped: List[List[Tuple[int, float, int]]] = []
        width = 1 if need_bias else 0
        for r in rows:
            entries: List[Tuple[int, float, int]] = []
            if need_bias:
                entries.append((0, 1.0, 0))  # bias field 0 (FFMModelDataFlow)
            for name, v in r.feats:
                gi = fmap.get(name)
                if gi is None:
                    continue  # filtered feature — dropped like handleLocalIdx
                fi = 0
                if fm is not None:
                    fi = fm.get(name.split(fdelim)[0], -1)
                    if fi < 0:
                        continue  # unknown field — dropped
                entries.append((gi, v, fi))
            mapped.append(entries)
            width = max(width, len(entries))
        width = max(width, 1)
        tv = None
        if nodes:
            # one vectorized replay over every kept entry — the same
            # apply_nodes kernel ingest's columnar path, the offline
            # predictors, and the serving pipeline share (transform/).
            # The bias entry has no node (nodes_from_stats excludes the
            # bias name), so replaying it is the identity.
            from ..transform.pipeline import TransformTable, apply_nodes

            flat_gi = np.fromiter(
                (e[0] for es in mapped for e in es),
                np.int64,
                sum(len(es) for es in mapped),
            )
            flat_v = np.fromiter(
                (e[1] for es in mapped for e in es), np.float64, len(flat_gi)
            )
            table = TransformTable.from_indexed(nodes, len(fmap))
            tv = apply_nodes(table, flat_gi, flat_v) if len(flat_gi) else flat_v
        idx = np.zeros((n, width), np.int32)
        val = np.zeros((n, width), np.float32)
        field = np.zeros((n, width), np.int32) if fm is not None else None
        k = 0
        for i, entries in enumerate(mapped):
            for j, (gi, v, fi) in enumerate(entries):
                idx[i, j] = gi
                val[i, j] = tv[k] if tv is not None else v
                k += 1
                if field is not None:
                    field[i, j] = fi
        y = np.asarray(
            [r.labels for r in rows], np.float32
        ).reshape((n, K)) if K > 1 else np.asarray([r.labels[0] for r in rows], np.float32)
        weight = np.asarray([r.weight for r in rows], np.float32)
        return SparseDataset(idx, val, y, weight, n_real=n, dim=len(fmap), field=field)

    # -- the whole flow ---------------------------------------------------

    def _resolve_feature_map(self, counts_fn) -> Dict[str, int]:
        """The dict branch shared by both load paths: load when just_evaluate
        / need_dict / continue_train finds a sidecar, else build from counts.

        Rank0 decides which branch applies (the sidecar existence check is a
        rank0-local fs fact — dumps are rank0-only), then every rank enters
        the same path: divergent branch picks would leave rank0 inside
        load_feature_map while peers enter finalize_feature_map's
        host_allgather collective, hanging the group (ADVICE r3)."""
        p = self.params
        model_dict_path = p.model.data_path + "_dict"
        from ..parallel.collectives import load_on_rank0

        def pick_dict_source():
            if p.loss.just_evaluate and self.fs.exists(model_dict_path):
                return [model_dict_path]
            if p.model.need_dict and p.model.dict_path:
                return [p.model.dict_path]
            if p.model.continue_train and self.fs.exists(model_dict_path):
                return [model_dict_path]
            return None

        src = load_on_rank0(pick_dict_source)
        if src is not None:
            return self.load_feature_map(src)  # rank0-read + broadcast inside
        return self.finalize_feature_map(counts_fn())

    def load(self) -> IngestResult:
        """The loadFlow equivalent (reference: dataflow/DataFlow.java:468).

        Dispatches to the columnar native-parser path when available (exact
        parity with the python path, tests/test_native_ingest.py); the python
        path remains for transform-hook / exotic-delimiter configs."""
        from . import native

        if (self.transform_hook is None
                and native.native_available()
                and native.supports_delims(self.params.data.delim)):
            return self._load_fast()
        return self._load_python()

    def _load_python(self) -> IngestResult:
        p = self.params

        def read(paths: Sequence[str]) -> Iterator[str]:
            return shard_read_lines(self.fs, p.data, paths)

        with obs_span("ingest.parse", split="train", path="python"):
            train_rows = self.parse_rows(
                read(p.data.train_paths), p.data.train_max_error_tol, is_train=True
            )
        with obs_span("ingest.dict"):
            fmap = self._resolve_feature_map(lambda: _counts_from_rows(train_rows))
        with obs_span("ingest.transform"):
            nodes = self.compute_transform_nodes(train_rows, fmap)
            if nodes:
                self.write_transform_sidecar(nodes, fmap)

        with obs_span("ingest.materialize", split="train"):
            train = self.to_dataset(train_rows, fmap, nodes)
        obs_inc("ingest.rows", train.n_real)
        test = None
        if p.data.test_paths:
            with obs_span("ingest.parse", split="test", path="python"):
                test_rows = self.parse_rows(
                    read(p.data.test_paths), p.data.test_max_error_tol, is_train=False
                )
            with obs_span("ingest.materialize", split="test"):
                test = self.to_dataset(test_rows, fmap, nodes)
            obs_inc("ingest.rows", test.n_real)

        # global label stats (reference: CoreData.globalSync y stats)
        K = max(self.n_labels, 2)
        y_real = np.zeros(K, np.int64)
        y_weight = np.zeros(K, np.float64)
        for r in train_rows:
            if len(r.labels) > 1:
                if 1.0 not in r.labels:
                    continue  # soft K-vector label: no class slot to count
                li = r.labels.index(1.0)
            else:
                li = int(r.labels[0])
            if 0 <= li < K:
                y_real[li] += 1
                y_weight[li] += r.weight
        return IngestResult(
            train=train,
            test=test,
            feature_map=fmap,
            transform_nodes=nodes,
            y_real_stat=y_real,
            y_weight_stat=y_weight,
        )

    # -- columnar fast path (native parser) -------------------------------

    def _parse_cols(self, paths, max_error_tol: int, is_train: bool) -> "_Cols":
        """Native parse + vectorized label expansion / hashing / y-sampling.
        Row and occurrence arrays come back in input order, matching the
        python path row-for-row (errors, dict order, rng consumption)."""
        from . import native

        p = self.params
        d = p.data.delim
        paths2, divisor, remainder = shard_plan(self.fs, p.data, paths)
        blk = native.parse_paths(
            self.fs, paths2, d.x_delim, d.y_delim, d.features_delim,
            d.feature_name_val_delim, divisor=divisor, remainder=remainder,
        )
        n_errors = blk.n_errors
        n = blk.n
        K = self.n_labels
        bad, y = native.expand_labels_columnar(blk.label_ptr, blk.labels, n, K)

        occ_row = np.repeat(np.arange(n), np.diff(blk.row_ptr))
        occ_name = blk.feat_ids.astype(np.int64)
        occ_val = blk.feat_vals.astype(np.float64)
        names: List[str] = blk.names

        if self.hash is not None and len(names):
            # hash per unique raw name, then per-row dedup-sum of signed
            # values in first-occurrence order (FeatureHash.hash_features)
            uniq: Dict[str, int] = {}
            hid_of = np.empty(len(names), np.int64)
            sign_of = np.empty(len(names), np.float64)
            for i, nm in enumerate(names):
                hn, sg = self.hash.hash_name(nm)
                hid_of[i] = uniq.setdefault(hn, len(uniq))
                sign_of[i] = sg
            signed = occ_val * sign_of[occ_name]
            hids = hid_of[occ_name]
            key = occ_row * np.int64(len(uniq)) + hids
            _, first_ix, inv = np.unique(key, return_index=True, return_inverse=True)
            sums = np.bincount(inv, weights=signed)
            order = np.argsort(first_ix, kind="stable")
            sel = first_ix[order]
            occ_row = occ_row[sel]
            occ_name = hids[sel]
            occ_val = sums[order]
            names = list(uniq)

        keep = ~bad
        weight = blk.weights.astype(np.float64)
        n_good = int(keep.sum())  # parse-valid lines, pre-subsample
        if is_train and p.data.y_sampling:
            # label-dependent subsampling with inverse-probability weight
            # correction (CoreData.yExtract). The host loop preserves the
            # python path's rng consumption order exactly: one rng.random()
            # per kept row whose label has a configured rate.
            ys = {k: float(v) for k, v in dict(p.data.y_sampling).items()}
            if K == 1:
                lidx = np.trunc(y).astype(np.int64)
                has1 = np.ones(n, bool)
            else:
                has1 = (y == 1.0).any(axis=1)
                lidx = np.argmax(y == 1.0, axis=1)
                # a K-vector label without an exact 1.0 cannot be sampled —
                # error line, like the python path's labels.index(1.0) raise
                newly_bad = keep & ~has1
                n_errors += int(newly_bad.sum())
                n_good -= int(newly_bad.sum())
                keep &= has1
            for i in np.flatnonzero(keep):
                rate = ys.get(str(int(lidx[i])))
                if rate is None:
                    continue
                weight[i] *= (1.0 / rate) if rate <= 1.0 else rate
                if self.rng.random() > rate:
                    keep[i] = False

        if n_errors > max_error_tol:
            raise ValueError(
                f"data error lines ({n_errors}) exceed max_error_tol "
                f"({max_error_tol})"
            )

        obs_inc("ingest.rows_parsed", float(keep.sum()))
        obs_inc("ingest.error_lines", float(n_errors))
        # rate over parse-valid lines BEFORE y_sampling drops: subsampling
        # a 99%-discarded majority class must not inflate the error rate
        health.check_ingest(
            "ingest.parse_native", int(n_errors), n_good, is_train=is_train
        )
        new_row = np.cumsum(keep) - 1
        occ_keep = keep[occ_row]
        return _Cols(
            weight=weight[keep].astype(np.float32),
            y=y[keep],
            occ_row=new_row[occ_row[occ_keep]],
            occ_name=occ_name[occ_keep],
            occ_val=occ_val[occ_keep],
            names=names,
        )

    def _cols_to_dataset(
        self,
        cols: "_Cols",
        fmap: Dict[str, int],
        nodes: Optional[Dict[int, TransformNode]] = None,
    ) -> SparseDataset:
        """Vectorized to_dataset: dict/field filtering, value transform,
        padded-ELL assembly."""
        p = self.params
        nodes = nodes or {}
        need_bias = p.model.need_bias
        n = len(cols.weight)
        gi_of = np.asarray([fmap.get(nm, -1) for nm in cols.names], np.int64)
        gi = gi_of[cols.occ_name] if len(cols.occ_name) else np.zeros(0, np.int64)
        keep = gi >= 0
        f = None
        if self.field_map is not None:
            fdelim = p.data.delim.field_delim
            fid_of = np.asarray(
                [self.field_map.get(nm.split(fdelim)[0], -1) for nm in cols.names],
                np.int64,
            )
            f = fid_of[cols.occ_name] if len(cols.occ_name) else np.zeros(0, np.int64)
            keep &= f >= 0
            f = f[keep]
        occ_row = cols.occ_row[keep]
        gi = gi[keep]
        val = cols.occ_val[keep].astype(np.float64)

        if nodes and len(gi):
            # the shared vectorized TransformNode replay (transform/) —
            # the identical kernel the serving pipeline executes, so the
            # trained values and the served values cannot drift
            from ..transform.pipeline import TransformTable, apply_nodes

            table = TransformTable.from_indexed(nodes, len(fmap))
            val = apply_nodes(table, gi, val)

        cnt = np.bincount(occ_row, minlength=n) if n else np.zeros(0, np.int64)
        delta = 1 if need_bias else 0
        width = max((int(cnt.max()) if n and len(cnt) else 0) + delta, 1)
        idx = np.zeros((n, width), np.int32)
        vmat = np.zeros((n, width), np.float32)
        fmat = np.zeros((n, width), np.int32) if self.field_map is not None else None
        if need_bias and n:
            vmat[:, 0] = 1.0  # bias index 0, field 0 (FFMModelDataFlow)
        rp = np.zeros(n + 1, np.int64)
        np.cumsum(cnt, out=rp[1:])
        j = np.arange(len(occ_row)) - rp[occ_row] + delta
        idx[occ_row, j] = gi
        vmat[occ_row, j] = val
        if fmat is not None:
            fmat[occ_row, j] = f
        K = self.n_labels
        y = cols.y if K > 1 else cols.y.reshape(-1)
        return SparseDataset(
            idx, vmat, y.astype(np.float32), cols.weight, n_real=n,
            dim=len(fmap), field=fmat,
        )

    def _load_fast(self) -> IngestResult:
        """Columnar loadFlow over the native parser — same pipeline, same
        results as _load_python, numpy-vectorized end to end."""
        p = self.params
        with obs_span("ingest.parse", split="train", path="native"):
            train = self._parse_cols(
                p.data.train_paths, p.data.train_max_error_tol, is_train=True
            )

        def counts() -> Dict[str, int]:
            c = np.bincount(train.occ_name, minlength=len(train.names))
            return {nm: int(c[i]) for i, nm in enumerate(train.names) if c[i] > 0}

        with obs_span("ingest.dict"):
            fmap = self._resolve_feature_map(counts)

        nodes: Dict[int, TransformNode] = {}
        if p.feature.transform.switch_on:
            nn = len(train.names)
            cnt = np.bincount(train.occ_name, minlength=nn).astype(np.int64)
            s1 = np.bincount(train.occ_name, weights=train.occ_val, minlength=nn)
            s2 = np.bincount(train.occ_name, weights=train.occ_val**2, minlength=nn)
            mn = np.full(nn, math.inf)
            mx = np.full(nn, -math.inf)
            if len(train.occ_name):
                np.minimum.at(mn, train.occ_name, train.occ_val)
                np.maximum.at(mx, train.occ_name, train.occ_val)
            stats = {
                nm: FeatureStat(cnt=int(cnt[i]), sum=float(s1[i]),
                                sum2=float(s2[i]), max=float(mx[i]), min=float(mn[i]))
                for i, nm in enumerate(train.names) if cnt[i] > 0
            }
            nodes = self.nodes_from_stats(stats, fmap)
            if nodes:
                self.write_transform_sidecar(nodes, fmap)

        with obs_span("ingest.materialize", split="train"):
            train_ds = self._cols_to_dataset(train, fmap, nodes)
        obs_inc("ingest.rows", train_ds.n_real)
        test_ds = None
        if p.data.test_paths:
            with obs_span("ingest.parse", split="test", path="native"):
                test = self._parse_cols(
                    p.data.test_paths, p.data.test_max_error_tol, is_train=False
                )
            with obs_span("ingest.materialize", split="test"):
                test_ds = self._cols_to_dataset(test, fmap, nodes)
            obs_inc("ingest.rows", test_ds.n_real)

        # global label stats (CoreData.globalSync y stats)
        K = max(self.n_labels, 2)
        y_real = np.zeros(K, np.int64)
        y_weight = np.zeros(K, np.float64)
        if self.n_labels == 1:
            li = np.trunc(train.y).astype(np.int64)
            valid = (li >= 0) & (li < K)
        else:
            has1 = (train.y == 1.0).any(axis=1)
            li = np.argmax(train.y == 1.0, axis=1)
            valid = has1 & (li >= 0) & (li < K)
        np.add.at(y_real, li[valid], 1)
        np.add.at(y_weight, li[valid], train.weight[valid].astype(np.float64))
        return IngestResult(
            train=train_ds,
            test=test_ds,
            feature_map=fmap,
            transform_nodes=nodes,
            y_real_stat=y_real,
            y_weight_stat=y_weight,
        )
