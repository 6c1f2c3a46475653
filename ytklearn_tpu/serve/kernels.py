"""Fused serve-side GBDT inference kernels + low-precision scoring tables.

The r9 serve path lowers the ensemble into stacked node arrays and walks
them with XLA gathers — correct everywhere, but every node visit pays ~5
gathered elements and TPU gathers run far off the strided path (the same
lesson that made `gbdt/hist.py` fuse the histogram gather, r6). This
module is that idiom pointed at inference:

  kernel layout   every tree re-laid as a PERFECT HEAP (Tree.heap_arrays):
                  slot p's children are 2p+1/2p+2, so the fixed-depth walk
                  needs no child pointers and the leaf value lives in the
                  last heap level only; leaves above it become always-go-
                  left pad chains whose last-level slot carries the value
  fused_scores    Pallas traversal kernel: node arrays resident in VMEM
                  (BlockSpec per tree-block), the rung's rows DMA'd in per
                  wave, every (tree, depth) step resolved with one-hot
                  select-reduces over the node/feature lanes instead of
                  gathers, all trees accumulated per row in ascending
                  order (strict left fold — bit-identical to the stacked
                  path at equal dtype). Off-TPU the kernel runs only under
                  the Pallas interpreter (tests); production CPU serving
                  downgrades (scorer.py's probe chain)
  binned tables   BinTable: per-feature sorted edge values — the DUMPED
                  training representatives (`<model>.bins.json`,
                  gbdt/binning.dump_bin_edges) when present, else the
                  ensemble's own split thresholds — plus `bin_rows` to bin
                  a request batch once (uint8/uint16, missing = sentinel)
                  and `pack_heap_nodes` to fold each node's edge RANK into
                  one int32 (feat 12b | rank+1 16b | default_left 1b).
                  With dumped edges the compare reproduces train-time
                  routing (nearest-representative, boundary ties round
                  up); with derived thresholds `bin < rank+1` is exactly
                  `value <= split` — bit-identical everywhere
  binned_scores_* three executions of the binned walk: the Pallas variant
                  (integer compares, TPU), a native C++ kernel
                  (native/ytk_serve.cpp — branchless, L1-blocked, OpenMP;
                  ~3x the XLA gather path single-threaded on CPU and
                  scales with cores), and an XLA fallback (packed single-
                  gather walk) that compiles everywhere

serve/scorer.py owns rung selection + the AOT probe downgrade chain
(fused -> stacked, binned: pallas|native -> XLA -> stacked); every
downgrade is a named `serve.downgrade.*` counter. docs/serving.md
"Fused inference kernel & precision rungs" is the operator story.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import knobs

log = logging.getLogger(__name__)

#: heap layout is 2^(depth+1)-1 slots per tree: past this depth the node
#: arrays stop fitting VMEM/caches and the scorer downgrades loudly
HEAP_DEPTH_CAP = 10
#: packed-node field widths (native + XLA binned walks share the layout)
FEAT_BITS = 12  # <= 4095 distinct serving features
RANK_BITS = 16  # <= 65534 edges per feature (uint16 bins)

_U8_SENTINEL = 0xFF
_U16_SENTINEL = 0xFFFF


# ---------------------------------------------------------------------------
# Heap-layout ensemble export
# ---------------------------------------------------------------------------


@dataclass
class HeapEnsemble:
    """Stacked kernel-layout node arrays for T trees (Tree.heap_arrays)."""

    feat: np.ndarray  # (T, H) int32 — serving column id per slot
    split: np.ndarray  # (T, H) float64 — +inf on pad slots (always left)
    dleft: np.ndarray  # (T, H) int32 — missing-value default direction
    inner: np.ndarray  # (T, H) bool — real split nodes (pads excluded)
    leaf: np.ndarray  # (T, LL) float64 — last-level leaf values (-0.0 pads)
    depth: int
    n_trees: int  # real tree count; rows past it are -0.0 pad trees

    @property
    def heap(self) -> int:
        return self.feat.shape[1]

    @property
    def last(self) -> int:
        return self.leaf.shape[1]


def build_heap(
    trees, vocab: Dict[str, int], depth_cap: int = HEAP_DEPTH_CAP,
    pad_trees_to: int = 8,
) -> Tuple[Optional[HeapEnsemble], str]:
    """Stack every tree's heap arrays; (None, reason) when the ensemble
    cannot take the kernel layout (too deep, too many features, no
    features at all) — the scorer downgrades to the stacked path then."""
    if not trees:
        return None, "empty ensemble"
    if not vocab:
        return None, "no split features (leaf-only ensemble)"
    if len(vocab) > (1 << FEAT_BITS) - 1:
        return None, f"{len(vocab)} features > packed-node limit"
    depth = max(max(t.max_depth() for t in trees), 1)
    if depth > depth_cap:
        return None, f"ensemble depth {depth} > heap cap {depth_cap}"
    T = len(trees)
    Tp = -(-T // pad_trees_to) * pad_trees_to
    H = (1 << (depth + 1)) - 1
    LL = 1 << depth
    feat = np.zeros((Tp, H), np.int32)
    split = np.full((Tp, H), np.inf, np.float64)
    dleft = np.ones((Tp, H), np.int32)
    inner = np.zeros((Tp, H), bool)
    # -0.0 pad values: x + (-0.0) == x for EVERY x (x + 0.0 flips -0.0),
    # so the pad trees keep the fold bit-exact
    leaf = np.full((Tp, LL), -0.0, np.float64)
    for ti, t in enumerate(trees):
        ids = [
            vocab[t.feat_name[nid]] if not t.is_leaf(nid) else -1
            for nid in range(t.n_nodes())
        ]
        arrs = t.heap_arrays(depth, feat_ids=ids)
        feat[ti] = arrs["feat"]
        split[ti] = arrs["split"]
        dleft[ti] = arrs["dleft"]
        inner[ti] = arrs["inner"]
        leaf[ti] = arrs["leaf"]
    return (
        HeapEnsemble(feat, split, dleft, inner, leaf, depth, T),
        "",
    )


# ---------------------------------------------------------------------------
# Bin tables: dumped training edges, or thresholds derived from the model
# ---------------------------------------------------------------------------


@dataclass
class BinTable:
    """Per-feature sorted edge values + the serve-side binning rule.

    mode "edges": values are the dumped training representatives; rows bin
    by the SAME nearest-representative rule as the training matrix
    (`gbdt/binning.bin_matrix` — re-stated here in f64 rather than called:
    bin_matrix runs on the f32 training matrix, and the native C twin
    must match this path bit-for-bit in f64; a rule-drift test pins the
    two against each other on exactly-representable values), node
    rank+1 = #edges <= split. Boundary ties round up exactly like
    training; off-boundary rows route identically to the float compare.

    mode "thresholds": values are the ensemble's own distinct split values
    per feature; bin = #thresholds < value, rank+1 = index(split)+1, and
    `bin < rank+1` IS `value <= split` — bit-identical everywhere."""

    values: List[np.ndarray]  # per serving column, ascending f64
    mode: str  # "edges" | "thresholds"
    dtype: np.dtype
    sentinel: int

    def flat(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(edges, offsets, counts) — the concatenated layout the native
        binning entry reads (cached; values are immutable)."""
        out = getattr(self, "_flat", None)
        if out is None:
            counts = np.asarray([len(v) for v in self.values], np.int64)
            offsets = np.zeros(len(self.values), np.int64)
            if len(counts):
                offsets[1:] = np.cumsum(counts)[:-1]
            edges = (
                np.ascontiguousarray(np.concatenate(self.values))
                if len(self.values)
                else np.zeros(0, np.float64)
            )
            out = (edges, offsets, counts)
            self._flat = out
        return out


def build_bin_table(
    trees, vocab: Dict[str, int],
    edges_by_name: Optional[Dict[str, np.ndarray]] = None,
) -> Tuple[Optional[BinTable], str]:
    """BinTable for the serving columns, or (None, reason).

    A dumped sidecar is used only when it covers every split feature AND
    every split value lies inside its feature's edge range — a stale
    sidecar (model retrained without one) silently misroutes, so it falls
    back to ensemble-derived thresholds with a warning instead."""
    F = len(vocab)
    splits_per_col: List[set] = [set() for _ in range(F)]
    for t in trees:
        for nid in range(t.n_nodes()):
            if not t.is_leaf(nid):
                splits_per_col[vocab[t.feat_name[nid]]].add(
                    float(t.split[nid])
                )
    mode = "thresholds"
    values: List[np.ndarray] = []
    if edges_by_name is not None:
        by_col: List[Optional[np.ndarray]] = [None] * F
        ok = True
        for name, j in vocab.items():
            e = edges_by_name.get(name)
            if e is None or len(e) == 0:
                log.warning(
                    "bin-edges sidecar misses feature %r; deriving "
                    "thresholds from the ensemble instead", name,
                )
                ok = False
                break
            e = np.unique(np.asarray(e, np.float64))
            if splits_per_col[j] and (
                min(splits_per_col[j]) < e[0]
                or max(splits_per_col[j]) > e[-1]
            ):
                log.warning(
                    "bin-edges sidecar looks stale for feature %r (split "
                    "outside the edge range); deriving thresholds from "
                    "the ensemble instead", name,
                )
                ok = False
                break
            by_col[j] = e
        if ok:
            values = [v for v in by_col]  # type: ignore[misc]
            mode = "edges"
    if mode == "thresholds":
        values = [
            np.asarray(sorted(s), np.float64)
            if s else np.zeros((1,), np.float64)
            for s in splits_per_col
        ]
    # +1 headroom: thresholds-mode bins range up to len(values[f])
    maxc = max((len(v) for v in values), default=1)
    if maxc + 1 >= _U16_SENTINEL:
        return None, f"{maxc} edges on one feature > uint16 bin budget"
    small = maxc + 1 < _U8_SENTINEL
    return (
        BinTable(
            values=values, mode=mode,
            dtype=np.dtype(np.uint8 if small else np.uint16),
            sentinel=_U8_SENTINEL if small else _U16_SENTINEL,
        ),
        "",
    )


def bin_rows(X: np.ndarray, table: BinTable) -> np.ndarray:
    """(B, F) raw f64 rows (NaN = missing) -> (B, F) bin indices in the
    table dtype, binned ONCE per batch; missing values get the sentinel.

    mode "thresholds": bin = #edges < value. mode "edges": the training
    nearest-representative rule (gbdt/binning.bin_matrix, in f64). The
    native entry (ytk_serve_bin_*) runs the identical f64 comparisons
    ~10x faster than the per-feature searchsorted loop; results are
    bit-equal by construction and test-pinned."""
    X = np.ascontiguousarray(X, np.float64)
    B, F = X.shape
    lib = _load()
    if lib is not None and F == len(table.values):
        edges, offsets, counts = table.flat()
        out = np.empty((B, F), table.dtype)
        fn = (
            lib.ytk_serve_bin_u8
            if table.dtype == np.uint8
            else lib.ytk_serve_bin_u16
        )
        nt = 1 if B < 64 else resolve_kernel_threads()
        fn(
            X.ctypes.data, B, F, edges.ctypes.data, offsets.ctypes.data,
            counts.ctypes.data, 0 if table.mode == "thresholds" else 1,
            table.sentinel, out.ctypes.data, nt,
        )
        return out
    nan = np.isnan(X)
    out = np.empty((B, F), np.int64)
    for f in range(F):
        v = table.values[f]
        col = X[:, f]
        i = np.searchsorted(v, col, side="left")
        if table.mode == "edges":
            cnt = len(v)
            over = col > v[-1]
            i = np.clip(i, 0, cnt - 1)
            mids = 0.5 * (v[np.maximum(i - 1, 0)] + v[i])
            i = np.where((i >= 1) & (col < mids) & ~over, i - 1, i)
            i = np.where(over, cnt - 1, i)
        out[:, f] = i
    out = out.astype(table.dtype)
    out[nan] = table.sentinel
    return np.ascontiguousarray(out)


def pack_heap_nodes(heap: HeapEnsemble, table: BinTable) -> np.ndarray:
    """(T, H) int32 packed node records for the native/XLA binned walks:
    feat (12b) | rank+1 (16b) | default_left (1b). rank+1 semantics:
    go_left iff bin < rank+1 (0 = always right); pad slots get the
    all-ones rank so every non-missing row keeps descending left."""
    rank1 = np.full(heap.feat.shape, (1 << RANK_BITS) - 1, np.int64)
    for f, v in enumerate(table.values):
        m = heap.inner & (heap.feat == f)
        if not m.any():
            continue
        side = "right" if table.mode == "edges" else "left"
        r = np.searchsorted(v, heap.split[m], side=side)
        if table.mode == "thresholds":
            r = r + 1  # bin < idx+1  <=>  #\{th < v\} <= idx  <=>  v <= split
        rank1[m] = r
    packed = (
        heap.feat.astype(np.int64)
        | (rank1 << FEAT_BITS)
        | (heap.dleft.astype(np.int64) << (FEAT_BITS + RANK_BITS))
    )
    return packed.astype(np.int32)


# ---------------------------------------------------------------------------
# Pallas fused traversal kernels (TPU; interpret=True drives them in tests)
# ---------------------------------------------------------------------------


def _pick_tree_block(T: int) -> int:
    for tb in (8, 4, 2, 1):
        if T % tb == 0:
            return tb
    return 1


def _walk_block(x_ref, f_ref, s_ref, d_ref, l_ref, out_ref, *,
                tb: int, depth: int, binned: bool, sentinel: int):
    """Shared Pallas body: one tree-block over the whole rung. One-hot
    select-reduces (nodes/features on sublanes, rows on lanes) stand in
    for gathers — Mosaic-legal and MXU/VPU-shaped; the accumulator is
    read-modify-written per tree so the fold order stays strictly
    tree-ascending across blocks (grid dim is "arbitrary" = sequential)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    H = f_ref.shape[1]
    LL = l_ref.shape[1]
    X = x_ref[...]  # (F, B) rows transposed: features on sublanes
    F, B = X.shape
    blk = pl.program_id(0)
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (F, 1), 0)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (LL, 1), 0)

    @pl.when(blk == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[0, :]
    zero = X.dtype.type(0)
    for t in range(tb):
        ft = f_ref[t, :][:, None]  # (H, 1)
        st = s_ref[t, :][:, None]
        dt = d_ref[t, :][:, None]
        lt = l_ref[t, :][:, None]  # (LL, 1)
        pos = jnp.zeros((1, B), jnp.int32)
        for _ in range(depth):
            oh = iota_h == pos  # (H, B): exactly one hit per column
            fv = jnp.sum(jnp.where(oh, ft, 0), axis=0, keepdims=True)
            sv = jnp.sum(jnp.where(oh, st, zero), axis=0, keepdims=True)
            dv = jnp.sum(jnp.where(oh, dt, 0), axis=0, keepdims=True)
            ohf = iota_f == fv  # (F, B)
            vv = jnp.sum(jnp.where(ohf, X, zero), axis=0, keepdims=True)
            if binned:
                go_left = jnp.where(vv == sentinel, dv > 0, vv < sv)
            else:
                go_left = jnp.where(jnp.isnan(vv), dv > 0, vv <= sv)
            pos = 2 * pos + 2 - go_left.astype(jnp.int32)
        ohl = iota_l == (pos - (LL - 1))
        contrib = jnp.sum(jnp.where(ohl, lt, l_ref.dtype.type(0)), axis=0)
        acc = acc + contrib
    out_ref[0, :] = acc


def _fused_call(xt, feat, sv, dleft, leaf, depth, binned, sentinel,
                interpret):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H = feat.shape
    LL = leaf.shape[1]
    F, B = xt.shape
    tb = _pick_tree_block(T)
    kernel = partial(
        _walk_block, tb=tb, depth=depth, binned=binned, sentinel=sentinel,
    )
    out = pl.pallas_call(
        kernel,
        grid=(T // tb,),
        in_specs=[
            pl.BlockSpec((F, B), lambda i: (0, 0)),  # the rung's row wave
            pl.BlockSpec((tb, H), lambda i: (i, 0)),  # node arrays ride
            pl.BlockSpec((tb, H), lambda i: (i, 0)),  # VMEM per block
            pl.BlockSpec((tb, H), lambda i: (i, 0)),
            pl.BlockSpec((tb, LL), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, B), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), leaf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(xt, feat, sv, dleft, leaf)
    return out[0]


def fused_scores(xt, feat, split, dleft, leaf, depth: int,
                 interpret: bool = False):
    """(B,) raw ensemble sums (no base/RF) from transposed rows xt (F, B)
    via the float fused kernel; dtype follows the inputs (f64 under the
    interpreter keeps the fold bit-identical to the stacked path).
    Traceable (callers jit it inside their kernel closures) and callable
    eagerly — the scorer's AOT probe runs it once un-jitted so a Mosaic
    failure surfaces at lowering, not mid-request."""
    return _fused_call(
        xt, feat, split, dleft, leaf, depth,
        binned=False, sentinel=0, interpret=interpret,
    )


def binned_scores_pallas(bt, feat, rank1, dleft, leaf, depth: int,
                         sentinel: int, interpret: bool = False):
    """Binned fused kernel: bt (F, B) int32 bin indices, rank1 (T, H)
    int32 (go_left iff bin < rank1), integer compares throughout."""
    return _fused_call(
        bt, feat, rank1, dleft, leaf, depth,
        binned=True, sentinel=sentinel, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# XLA binned fallback: packed single-gather heap walk, compiles everywhere
# ---------------------------------------------------------------------------


def make_binned_xla(packed: np.ndarray, leaf: np.ndarray, depth: int,
                    sentinel: int):
    """fn(bins (B, F) int32) -> (B,) raw sums. One packed-node gather +
    one row-bin gather per depth step (the stacked float path pays ~5),
    and the exact fold is UNROLLED — in-context the 500-step fori_loop
    measured ~40% of the kernel on CPU while the unrolled chain of adds
    costs its flops only."""
    import jax.numpy as jnp

    T, H = packed.shape
    LL = leaf.shape[1]
    packed_j = jnp.asarray(packed)
    leaf_j = jnp.asarray(leaf)

    def run(bw):
        B = bw.shape[0]
        rows = jnp.arange(B)[:, None]
        tids = jnp.arange(T)[None, :]
        pos = jnp.zeros((B, T), jnp.int32)
        for _ in range(depth):
            pk = packed_j[tids, pos]
            fv = pk & ((1 << FEAT_BITS) - 1)
            rank1 = (pk >> FEAT_BITS) & ((1 << RANK_BITS) - 1)
            dl = (pk >> (FEAT_BITS + RANK_BITS)) & 1
            vv = bw[rows, fv]
            go_left = jnp.where(vv == sentinel, dl > 0, vv < rank1)
            pos = 2 * pos + 2 - go_left.astype(jnp.int32)
        contrib = leaf_j[tids, pos - (LL - 1)]  # (B, T)
        s = jnp.zeros((B,), leaf_j.dtype)
        for t in range(T):  # strict left fold, unrolled
            s = s + contrib[:, t]
        return s

    return run


# ---------------------------------------------------------------------------
# Native C++ binned kernel (native/ytk_serve.cpp) — the io/native.py idiom:
# compiled on demand with g++, cached by source+flags hash, loudly optional.
# ---------------------------------------------------------------------------

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native", "ytk_serve.cpp",
)

_lock = threading.Lock()
_lib = None
_lib_failed = False


def _build() -> Optional[str]:
    """Build (or find) the hash-named .so (io/native.py idiom); None when
    no compile succeeds — serving then stays on the XLA walk."""
    from ..io.native import CXX, ensure_so

    # OpenMP first (row-parallel scoring), plain second (the pragma is
    # ignored without it — single-threaded but still branchless+blocked)
    for cmd in (CXX + ["-fopenmp"], CXX):
        try:
            return ensure_so(_SRC, cmd, "libytkserve")
        except (subprocess.SubprocessError, OSError) as e:
            err = getattr(e, "stderr", b"")
            log.warning(
                "native serve kernel build failed (%s): %s", e,
                err.decode()[:300] if err else "",
            )
    return None


def _load():
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        if knobs.get_bool("YTK_NO_NATIVE"):
            _lib_failed = True
            return None
        # ytklint: allow(deep-blocking-under-lock) reason=first-touch build serialization is the point — concurrent scorer lowerings must wait for the ONE compiler run instead of racing N compiles of the same .so (io/native.py precedent)
        so = _build()
        if so is None:
            _lib_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("native serve kernel load failed: %s", e)
            _lib_failed = True
            return None
        for name in ("ytk_serve_score_u8", "ytk_serve_score_u16"):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_int32,
            ]
        for name in ("ytk_serve_bin_u8", "ytk_serve_bin_u16"):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_int32,
            ]
        _lib = lib
        return _lib


def native_serve_available() -> bool:
    return _load() is not None


def resolve_kernel_threads() -> int:
    """YTK_SERVE_KERNEL_THREADS, or min(8, cores) — rows parallelize
    embarrassingly but a serving box shares cores with the batcher/HTTP
    threads, so the default stays bounded."""
    n = knobs.get_int("YTK_SERVE_KERNEL_THREADS") or 0
    if n > 0:
        return n
    return max(1, min(8, os.cpu_count() or 1))


def native_binned_scores(
    bins: np.ndarray, packed: np.ndarray, leaf: np.ndarray, depth: int,
    sentinel: int, n_threads: int,
) -> np.ndarray:
    """(B,) raw f64 ensemble sums from (B, F) u8/u16 bins; the per-row
    fold order matches batch_scores exactly (ascending trees, f64)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native serve kernel unavailable")
    B, F = bins.shape
    T, H = packed.shape
    LL = leaf.shape[1]
    out = np.zeros((B,), np.float64)
    fn = (
        lib.ytk_serve_score_u8
        if bins.dtype == np.uint8
        else lib.ytk_serve_score_u16
    )
    if bins.dtype not in (np.uint8, np.uint16):
        raise TypeError(f"bins dtype {bins.dtype} not u8/u16")
    assert bins.flags.c_contiguous and packed.flags.c_contiguous
    assert leaf.flags.c_contiguous
    nt = 1 if B < 64 else n_threads
    fn(
        bins.ctypes.data, B, F, packed.ctypes.data, leaf.ctypes.data,
        T, H, LL, depth, sentinel, out.ctypes.data, nt,
    )
    return out
