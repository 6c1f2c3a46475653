"""Device-resident GBDT tree growth — the whole tree as ONE XLA program.

Rebuild of reference optimizer/gbdt/DataParallelTreeMaker.java:229-653
(expand queue, histogram build + reduce-scatter, sibling subtraction via
HistogramPool, split enumeration, sample position update) re-architected
for the TPU's cost model: every device->host sync stalls the enqueue
pipeline for its latency, so the reference's host-driven expand loop
(host pops a queue node, launches a histogram, reads back split stats —
hundreds of syncs per tree) would spend its time waiting. Instead the
full growth loop runs on device inside lax.while_loop; the host enqueues
one program per tree and reads nothing back until training ends.

Growth is organized in WAVES of up to `spec.wave` node expansions:
  1. select expandable frontier nodes — by (depth, node id) for the level
     policy (exactly the reference's level order, including the leaf-
     budget count-off), by descending best-gain for the loss policy
     (wave=1 is exactly the reference's best-first; wave=T>1 relaxes the
     pop granularity to T for throughput — T gain-ordered splits per
     histogram pass instead of one)
  2. record the splits into fixed-size tree arrays, allocate children
  3. route samples: per wave node, one bins_t row slice + compare
     (SamplePositionData.resetPosition:115 without the re-sort)
  4. histogram the SMALLER child of each split via the Pallas one-hot
     matmul kernel; derive the sibling by pool subtraction
     (HistogramPool's trick, data/gbdt/HistogramPool.java)
  5. enumerate best splits for all new children (split_kernel) and
     refresh the frontier arrays.

All arrays are fixed-shape: tree fields are (max_nodes,), the histogram
pool is (max_nodes, F, B, 3), the wave is padded to `spec.wave` with
masked no-op slots (scatter mode="drop").
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import scope, subscope
from .hist import (
    BMG_DEFAULT,
    compact_indices,
    gather_table,
    hist_wave,
    hist_wave_gather,
    onehot_split,
    tile_bins,
)
from .route import route_kernel_holds, route_wave

BIG32 = np.int32(2**31 - 1)


def wave_log_rows(max_nodes: int) -> int:
    """Rows of the per-tree wave log grow() returns (one per histogram
    pass: root + slow-start ramp + growth waves; trainer buffers and
    ablation scripts size their arrays with this)."""
    return max_nodes + 8


# ---------------------------------------------------------------------------
# Gain / leaf-value formulas (reference: UpdateStrategy.java:64-83)
# ---------------------------------------------------------------------------


def _threshold_l1(g, l1):
    return jnp.where(g > l1, g - l1, jnp.where(g < -l1, g + l1, 0.0))


def make_gain_fns(l1: float, l2: float, min_h: float, max_abs: float):
    def node_value(G, H):
        t = _threshold_l1(G, l1) if l1 > 0 else G
        val = -t / (H + l2)
        if max_abs > 0:
            val = jnp.clip(val, -max_abs, max_abs)
        return jnp.where(H < min_h, 0.0, val)

    def gain(G, H):
        if max_abs <= 0:
            t = _threshold_l1(G, l1) if l1 > 0 else G
            out = t * t / (H + l2)
        else:
            v = node_value(G, H)
            out = -2.0 * (G * v + 0.5 * (H + l2) * v * v + l1 * jnp.abs(v))
        return jnp.where(H < min_h, 0.0, out)

    return gain, node_value


@partial(jax.jit, static_argnames=("cfg",))
def split_kernel(hist, feat_mask, cfg, ranges=None, totals=None):
    """Best split per node from (N, F, B, 3) histograms.

    Returns per-node: (loss_chg, flat_idx, slot_left, GL, HL, CL, GR, HR, CR)
    (reference: DataParallelTreeMaker.enumerateSplit:598-637 — empty slots
    skipped, split interval [last nonempty, current], child-hessian guards,
    gain vs root; first-max argmax reproduces SplitInfo.needReplace:99's
    lower-slot tie-break).

    ranges: optional (range_lo, range_hi) (F, B) int32 tables for EFB
    bundle columns — range_lo[f, s]/range_hi[f, s] bound the member
    feature's slot range containing s (lo=0/hi=B-1 for plain columns).
    A bundled column concatenates its members' nonzero bins after a
    shared default bin 0, so a boundary s inside member j must count the
    member's DEFAULT rows (node total minus j's nonzero-range sum) on the
    left — LightGBM's per-feature sub-histogram enumeration as a closed
    form over the bundle cumsum: left_j(s) = C(s) + (total - C(hi_j+1)).
    With hi = B-1 the correction is identically zero, so plain columns
    keep the original math bit-for-bit.

    totals: optional (G, H) (N, 1) node totals for the node's own gain;
    default: feature 0's bin-sum (see node_totals)."""
    l1, l2, min_h, max_abs = cfg
    N, F, B, _ = hist.shape
    G, H, C = hist[..., 0], hist[..., 1], hist[..., 2]
    gain, _ = make_gain_fns(l1, l2, min_h, max_abs)

    # exclusive cumsums: stats strictly left of boundary slot j
    CGi = jnp.cumsum(G, axis=-1)  # inclusive
    CHi = jnp.cumsum(H, axis=-1)
    CCi = jnp.cumsum(C, axis=-1)
    GL = CGi - G
    HL = CHi - H
    CL = CCi - C
    Gt = jnp.sum(G, axis=-1, keepdims=True)
    Ht = jnp.sum(H, axis=-1, keepdims=True)
    Ct = jnp.sum(C, axis=-1, keepdims=True)

    nonempty = C > 0
    ne_incl = jnp.cumsum(nonempty.astype(jnp.int32), axis=-1)
    # ytklint: allow(host-sync-in-jit) reason=`ranges is None` is static pytree dispatch (None vs arrays picks the compiled variant), not a traced comparison
    if ranges is None:
        has_prev = (ne_incl - nonempty) > 0
    else:
        rlo, rhi = ranges  # (F, B) i32, broadcast over nodes

        def at_hi(A):  # inclusive cumsum at the member range's end == C(hi+1)
            return jnp.take_along_axis(
                A, jnp.broadcast_to(rhi[None], A.shape), axis=-1
            )

        def at_lo_excl(A_incl, A):  # exclusive cumsum at lo == C(lo)
            ex = A_incl - A
            return jnp.take_along_axis(
                ex, jnp.broadcast_to(rlo[None], ex.shape), axis=-1
            )

        # member-default stats fold into the left side: total - C(hi+1)
        GL = GL + (Gt - at_hi(CGi))
        HL = HL + (Ht - at_hi(CHi))
        CL = CL + (Ct - at_hi(CCi))
        # per-member has_prev: a nonempty slot in [lo, s), or a nonempty
        # member default bin (rows of this member's zero value + every
        # other member's rows)
        ne_in_range = (ne_incl - nonempty) - at_lo_excl(ne_incl, nonempty) > 0
        dflt_cnt = Ct - (at_hi(CCi) - at_lo_excl(CCi, C))
        has_prev = ne_in_range | (dflt_cnt > 0)
    GR, HR, CR = Gt - GL, Ht - HL, Ct - CL
    valid = nonempty & has_prev & (HL >= min_h) & (HR >= min_h)
    valid = valid & feat_mask[None, :, None]

    g0, h0 = node_totals(hist) if totals is None else totals
    root_gain = gain(g0, h0)

    loss_chg = gain(GL, HL) + gain(GR, HR) - root_gain[:, :, None]
    loss_chg = jnp.where(valid, loss_chg, -jnp.inf)

    flat = loss_chg.reshape(N, F * B)
    best = jnp.argmax(flat, axis=-1)  # first max -> lowest (f, slot) tie-break
    best_chg = jnp.take_along_axis(flat, best[:, None], axis=-1)[:, 0]

    # last nonempty slot strictly before j (the split interval's left end)
    idxs = jnp.where(nonempty, jnp.arange(B)[None, None, :], -1)
    lastne_incl = jax.lax.cummax(idxs, axis=2)
    lastne = jnp.concatenate(
        [jnp.full((N, F, 1), -1, lastne_incl.dtype), lastne_incl[:, :, :-1]], axis=2
    )
    # ytklint: allow(host-sync-in-jit) reason=`ranges is not None` is static pytree dispatch, not a traced comparison
    if ranges is not None:
        # clamp to the member range: lo-1 encodes "the member default bin"
        # (unbundles to the original feature's zero bin)
        lastne = jnp.maximum(lastne, (rlo - 1)[None])
    lastne = lastne.reshape(N, F * B)
    slot_left = jnp.take_along_axis(lastne, best[:, None], axis=-1)[:, 0]

    def pick(A):
        return jnp.take_along_axis(A.reshape(N, F * B), best[:, None], axis=-1)[:, 0]

    return (
        best_chg,
        best.astype(jnp.int32),
        slot_left.astype(jnp.int32),
        pick(GL),
        pick(HL),
        pick(CL),
        pick(GR),
        pick(HR),
        pick(CR),
    )


def node_totals(hist):
    """(G, H) (N, 1) node totals: every active sample hits every feature's
    histogram, so feature 0's bin-sum is the node total — up to the f32
    rounding of that feature's own bin order."""
    return (
        jnp.sum(hist[..., 0], axis=-1, keepdims=True)[:, 0:1, 0],
        jnp.sum(hist[..., 1], axis=-1, keepdims=True)[:, 0:1, 0],
    )


# ---------------------------------------------------------------------------
# The growth engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GrowSpec:
    """Static shape/config for one tree-growth program."""

    F: int
    B: int
    max_nodes: int  # tree array capacity (2*max_leaves-1 or full level tree)
    wave: int  # node expansions per wave (loss policy: best-first pop width)
    policy: str  # "level" | "loss"
    max_depth: int  # <=0 = unlimited
    max_leaves: int  # <=0 = unlimited
    lr: float
    l1: float
    l2: float
    min_h: float
    max_abs: float
    min_split_loss: float
    min_split_samples: float
    bm: int = 16384  # keep in sync with hist.BM_DEFAULT (trainer padding)
    precision: str = "bf16"  # histogram operands: "bf16" | "f32" | "int8"
    # histogram and routing kernels' family: "pallas" (Mosaic, the chip) |
    # "dense" (einsum / XLA twins, where Mosaic can't compile); resolved
    # once from the platform (GBDTTrainer._grow_spec), asked nowhere below
    kernels: str = "pallas"
    # leaf-partitioned histogram passes: once the frontier's waves need few
    # rows, compact the smaller-child rows into a static budget and
    # histogram only those — wave cost scales with rows-in-wave instead of
    # all n (the LightGBM data-partition idea; reference hot loop
    # HistogramBuilder.java:72-90 likewise iterates node intervals only).
    # `ladder` lists the budget divisors, () = no partitioned pass; growth
    # runs as phase-separated while_loops (full scan while waves are big,
    # then each budget, then a full-scan safety tail) because lax.cond
    # around Mosaic kernels is a compile catastrophe on the current
    # toolchain.
    ladder: Tuple[int, ...] = (8, 32)
    # fused compact+gather+histogram kernel (hist.hist_wave_gather): budget
    # rungs at or under `fused_max_rows` skip the XLA (R, F) row gather +
    # transpose entirely — the kernel DMAs each selected row HBM->VMEM and
    # accumulates in place. Rungs above the cap keep the XLA gather (the
    # fused kernel's per-row DMA issue loop is O(R) scalar work, so huge
    # budgets would pay more in descriptors than they save in MACs); 0 =
    # every rung takes the XLA gather. `fused_interpret` runs the fused
    # kernel through the Pallas interpreter in the dense family, and in the
    # Pallas family every kernel of the growth program — equivalence tests
    # of the REAL kernel logic on the CPU mesh.
    fused_max_rows: int = 1 << 18
    fused_interpret: bool = False
    bm_g: int = BMG_DEFAULT
    # GOSS (gradient-based one-side sampling, LightGBM §4): per tree,
    # keep the top goss_a fraction of rows by |g| (jax.lax.top_k), sample
    # the remainder at rate goss_b with a deterministic counter-based
    # draw (threefry fold_in on the round/group key — no host RNG), and
    # amplify the sampled rows' g/h by 1/goss_b. The kept set is
    # compacted into a static (a + b(1-a))-sized fit matrix that the
    # whole growth program runs on, so every histogram pass — full-scan
    # phases included — costs O(sampled rows); the full matrix rides
    # along as an aux set purely for final leaf assignment. goss_a >= 1
    # disables (the bit-identical unsampled path). goss_scale is the
    # caller's real-row fraction of the padded sample axis (top_k needs a
    # STATIC k, so the fractions apply to scale*n instead of the padded
    # n — without it a heavily-padded shard would "sample" every real
    # row); the include re-mask guarantees padding is never selected
    # either way.
    goss_a: float = 1.0
    goss_b: float = 0.0
    goss_scale: float = 1.0

    @property
    def depth_cap(self) -> int:
        return self.max_depth if self.max_depth > 0 else self.max_nodes

    @property
    def leaf_cap(self) -> int:
        # unlimited -> whatever fits the fixed arrays (nodes = 2*leaves-1)
        return self.max_leaves if self.max_leaves > 0 else (self.max_nodes + 1) // 2

    # What the width decides, from F, B, bm and the family alone (the rungs
    # below are the trainer's: GBDTTrainer._grow_spec sets `ladder` and
    # `fused_max_rows` from hist.fused_holds).
    @property
    def route(self) -> str:
        """The family route.route_wave takes: the one-pass kernel where its
        block holds all F features' bins of bm rows, else (and wherever the
        family is dense) a bins row a slot on the untiled matrix."""
        if self.kernels == "pallas" and route_kernel_holds(self.F, self.bm):
            return "pallas"
        return "dense"

    @property
    def packed(self) -> bool:
        """Whether the full-scan kernel's tiles are hist.tile_bins' packed
        words: one-byte bins that the routing kernel does not read."""
        return (
            self.kernels == "pallas" and self.route == "dense"
            and self.B <= 256
        )

    def factored_passes(self) -> int:
        """The full-scan passes a tree whose kernel factors the bin one-hot
        (hist.onehot_split > 1), of the root's and the slow start's, whose
        widths are fixed (1, then 1, 2, 4, ... below the wave): the Pallas
        kernel at bf16 or f32 (the int8 kernel and the dense twin never)."""
        if self.kernels != "pallas" or self.precision == "int8":
            return 0
        w = self.wave
        widths = [1] + [1 << k for k in range(w.bit_length()) if 1 << k < w]
        return sum(onehot_split(n, self.B) > 1 for n in widths)

    def goss_sizes(self, n_full: int) -> Tuple[int, int, int]:
        """GOSS's static sizes over `n_full` (padded, per-shard) rows: (top
        rows k_a, sampled remainder rows k_b, width R_fit of the compacted
        fit matrix). Counted over the REAL rows (goss_scale discounts
        padding; the engine re-masks so padding never leaks)."""
        gunit = self.bm if self.kernels == "pallas" else 128
        n_eff = max(1, min(n_full, int(np.ceil(self.goss_scale * n_full))))
        k_a = max(1, min(n_eff, int(np.ceil(self.goss_a * n_eff))))
        k_b = 0
        if self.goss_b > 0.0:
            k_b = min(n_eff - k_a, int(np.ceil(self.goss_b * (n_eff - k_a))))
        R_fit = max(gunit, -(-(k_a + k_b) // gunit) * gunit)
        return k_a, k_b, min(R_fit, n_full)

    def rungs(self, n: int) -> Tuple[Tuple[int, str], ...]:
        """The partitioned passes the growth program builds over `n` fit
        rows (per shard), ascending ((R, impl), ...): static row budget R,
        and "fused" (compact+gather+histogram in one Pallas kernel) or
        "xla" (explicit row gather + the full-scan kernel: the only option
        above fused_max_rows, where per-row DMA issue would dominate). A
        wave hists only smaller children, so ceil(n/2) always fits the
        largest budget. () = every pass is a full scan."""
        can_fuse = self.fused_max_rows > 0 and (
            self.kernels == "pallas" or self.fused_interpret
        )
        unit_xla = self.bm if self.kernels == "pallas" else 128
        out = {}
        for div in self.ladder:
            want = -(-n // div)  # ceil(n / div)
            fuse = can_fuse and want <= self.fused_max_rows
            unit = self.bm_g if fuse else unit_xla
            R = max(-(-want // unit) * unit, unit)
            if R < n:
                out.setdefault(R, "fused" if fuse else "xla")
        return tuple(sorted(out.items()))

    def leaf_lookup(self, kernel_max_nodes: int) -> str:
        """The family route.leaf_values takes for this tree size at the end
        of a tree: "pallas" (the one-pass kernel, whose cost grows with
        max_nodes / 128) up to `kernel_max_nodes` in the Pallas family,
        "dense" (XLA's gather, flat in the table's size) above it and
        wherever the family is dense."""
        if self.kernels == "pallas" and self.max_nodes <= kernel_max_nodes:
            return "pallas"
        return "dense"


class TreeArrays(NamedTuple):
    """Fixed-shape device tree (mirrors the host Tree fields that training
    needs; converted to gbdt.tree.Tree after the final fetch)."""

    feat: jnp.ndarray  # (M,) i32, -1 = leaf
    slot: jnp.ndarray  # (M,) i32 routing threshold (last nonempty before split)
    slot_r: jnp.ndarray  # (M,) i32 split interval right end (value conversion)
    left: jnp.ndarray  # (M,) i32
    right: jnp.ndarray  # (M,) i32
    leaf: jnp.ndarray  # (M,) f32 (lr-scaled)
    gain: jnp.ndarray  # (M,) f32
    hess: jnp.ndarray  # (M,) f32
    cnt: jnp.ndarray  # (M,) f32
    depth: jnp.ndarray  # (M,) i32
    n_nodes: jnp.ndarray  # () i32


class _Frontier(NamedTuple):
    chg: jnp.ndarray  # (M,) f32, -inf = none
    flat: jnp.ndarray  # (M,) i32 best f*B+slot
    slotl: jnp.ndarray  # (M,) i32
    GL: jnp.ndarray
    HL: jnp.ndarray
    CL: jnp.ndarray
    GR: jnp.ndarray
    HR: jnp.ndarray
    CR: jnp.ndarray
    active: jnp.ndarray  # (M,) bool


def make_grow_tree(spec: GrowSpec, mesh=None, axis: str = "data", ranges=None):
    """Build the jittable grow(bins_t, include, g, h, feat_mask[, aux, key]) fn.

    aux: optional (bins_t_extra, ...) tuple of extra transposed bin
    matrices (e.g. the test set) whose row positions are routed through
    the same splits; their final leaf assignment comes back alongside.
    key: PRNG key for the GOSS remainder draw (required semantics only
    when spec.goss_a < 1 and goss_b > 0; defaults to PRNGKey(0)). Under a
    mesh each shard folds in its axis index, so per-shard draws are
    independent and deterministic.
    ranges: optional (range_lo, range_hi) GLOBAL (F, B) int32 EFB member-
    range tables (see split_kernel); sliced per shard for enumeration,
    used whole for routing.

    With spec.goss_a < 1 the returned pos is the leaf assignment of the
    COMPACTED fit rows; the full training matrix is routed as the first
    aux entry, so callers read the train positions from aux_pos[0] and
    their own aux sets from aux_pos[1:].

    Returns (TreeArrays, pos_final, aux_pos_final, wave_log) where
    wave_log (max_nodes+8, 5) f32 records per histogram pass
    [rows_scanned, rows_needed, splits, hist_width, rows_sampled] — the
    roofline and O(wave rows) ablation record (row 0 = root pass; rows
    with hist_width 0 are unused slots; rows_sampled is the GOSS-kept
    row count, == the included-row count when GOSS is off; row counts
    are per-shard under a mesh, exact on one device).

    With a mesh of >1 devices the SAME growth program runs under
    `shard_map` over row shards — each device feeds its local rows to the
    SAME Pallas/dense histogram and routing kernels as mesh=1, partial
    histograms are combined by `psum_scatter` so each device owns a
    contiguous feature slice of every node histogram (the reduce-scatter
    ownership of reference HistogramBuilder.java:95), split enumeration
    runs only on the owned slice (DataParallelTreeMaker.java:598-653),
    and the global best split per node is merged with `pargmax_tuple`
    (SplitInfo.needReplace semantics: lower rank = lower global feature
    block on ties, reproducing single-device first-max tie-breaks).
    Caller contract for mesh>1: spec.F divisible by the device count
    (pad features + feat_mask), sample axis divisible by (devices x
    spec.bm) on TPU.
    """
    n_shards = 1 if mesh is None else int(mesh.devices.size)
    grow = _build_grow(spec, n_shards, axis, ranges)
    if n_shards == 1:
        return grow

    from jax.sharding import PartitionSpec as P

    def grow_sharded(bins_t, include, g, h, feat_mask, aux=(), key=None):
        if key is None:
            key = jax.random.PRNGKey(0)

        def f(bins_t, include, g, h, feat_mask, aux, key):
            return grow(bins_t, include, g, h, feat_mask, aux=aux, key=key)

        return jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(
                P(None, axis), P(axis), P(axis), P(axis), P(axis),
                P(None, axis), P(),
            ),
            # wave_log is replicated: rows/splits/width are static or come
            # from the globally-merged frontier stats
            out_specs=(P(), P(axis), P(axis), P()),
            check_vma=False,
        )(bins_t, include, g, h, feat_mask, tuple(aux), key)

    return grow_sharded


def _build_grow(spec: GrowSpec, n_shards: int = 1, axis: str = "data", ranges=None):
    """The growth program body; n_shards>1 = running inside shard_map."""
    M, NW, F, B = spec.max_nodes, spec.wave, spec.F, spec.B
    F_loc = F // max(n_shards, 1)
    assert F_loc * max(n_shards, 1) == F, (F, n_shards)
    cfg = (spec.l1, spec.l2, spec.min_h, spec.max_abs)
    _, node_value = make_gain_fns(*cfg)
    iota_m = jnp.arange(M, dtype=jnp.int32)
    if ranges is not None:
        rlo_g = jnp.asarray(ranges[0], jnp.int32)  # (F, B) global tables
        rhi_g = jnp.asarray(ranges[1], jnp.int32)
        assert rlo_g.shape == (F, B), (rlo_g.shape, F, B)
    else:
        rlo_g = rhi_g = None

    if n_shards > 1:
        from ..parallel.collectives import pargmax_tuple, psum, psum_scatter

        def combine_hist(local):
            """Partial (N, F, B, 3|i32) -> globally-summed owned F-slice."""
            return psum_scatter(local, axis, tiled=True, scatter_dimension=1)

        def local_ranges():
            """This shard's contiguous F-slice of the EFB range tables
            (hi/lo values are slot indices WITHIN a column's own bin
            axis, so slicing along F needs no re-offsetting)."""
            if rlo_g is None:
                return None
            dev = jax.lax.axis_index(axis)
            start = (dev * F_loc, jnp.zeros((), jnp.int32))
            return (
                jax.lax.dynamic_slice(rlo_g, start, (F_loc, B)),
                jax.lax.dynamic_slice(rhi_g, start, (F_loc, B)),
            )

        def best_splits(hists, fmask_loc, ranges_loc=None):
            """split_kernel on the owned slice + global pargmax merge.

            Local flat indices are offset into global (f, slot) coords;
            pargmax's lower-rank tie-break equals the single-device
            first-max tie-break because feature slices are contiguous."""
            # every shard subtracts rank 0's node gain — the single-device
            # program's (its feature 0 is rank 0's first owned feature).
            # Each shard's own first feature gives the same totals only up
            # to f32 rounding, and an ulp between shards reorders gain-
            # ordered selection: on four chips at the Higgs width the mesh
            # tree came out different from the single-chip tree.
            with scope("gbdt.split"):
                dev = jax.lax.axis_index(axis)
                g0, h0 = node_totals(hists)
                tot = psum(jnp.where(dev == 0, jnp.stack([g0, h0]), 0.0), axis)
                out = split_kernel(
                    hists, fmask_loc, cfg, ranges_loc, totals=(tot[0], tot[1])
                )
                gflat = out[1] + dev * (F_loc * B)
                chg, payload = pargmax_tuple(out[0], (gflat,) + out[2:], axis)
                return (chg,) + payload
    else:

        def combine_hist(local):
            return local

        def local_ranges():
            return None if rlo_g is None else (rlo_g, rhi_g)

        def best_splits(hists, fmask_loc, ranges_loc=None):
            with scope("gbdt.split"):
                return split_kernel(hists, fmask_loc, cfg, ranges_loc)

    def can_split(fr: _Frontier, tr: TreeArrays, leaves):
        ok = fr.active & jnp.isfinite(fr.chg) & (fr.chg > spec.min_split_loss)
        ok &= (fr.CL + fr.CR) >= spec.min_split_samples
        ok &= (fr.HL + fr.HR) >= 2.0 * spec.min_h
        ok &= tr.depth < spec.depth_cap
        # capacity: children must fit the fixed arrays
        return ok & (leaves < spec.leaf_cap)

    def select(ok, fr: _Frontier, tr: TreeArrays, nw: int):
        if spec.policy == "level":
            k1 = jnp.where(ok, tr.depth, BIG32)
            _, sel = jax.lax.sort((k1, iota_m), num_keys=2)
        else:
            k1 = jnp.where(ok, -fr.chg, jnp.inf)
            _, sel = jax.lax.sort((k1, iota_m), num_keys=2)
        sel = sel[:nw]
        return sel, ok[sel]

    def grow(bins_t, include, g, h, feat_mask, aux=(), key=None):
        ranges_loc = local_ranges()
        goss_on = 0.0 < spec.goss_a < 1.0
        goss_rows = None  # per-shard GOSS-kept row count (wave-log col 4)
        if goss_on:
            n_full = bins_t.shape[1]
            k_a, k_b, R_fit = spec.goss_sizes(n_full)
            if key is None:
                key = jax.random.PRNGKey(0)
            if n_shards > 1:
                # independent, deterministic per-shard draws
                key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            # top-a by |g|: exact-k via index scatter (top_k's lowest-index
            # tie-break keeps this deterministic); padding/excluded rows
            # carry -1 and sort last, the & include re-mask drops any that
            # leaked in when a*n_pad exceeds the real row count
            absg = jnp.where(include, jnp.abs(g), -1.0)
            _, idx_top = jax.lax.top_k(absg, k_a)
            keep = (
                jnp.zeros((n_full,), bool).at[idx_top].set(True) & include
            )
            if k_b > 0:
                u = jax.random.uniform(key, (n_full,))
                rest = include & ~keep
                _, idx_r = jax.lax.top_k(jnp.where(rest, u, -1.0), k_b)
                rmask = jnp.zeros((n_full,), bool).at[idx_r].set(True) & rest
                amp = jnp.float32(1.0 / spec.goss_b)
                g = jnp.where(rmask, g * amp, g)
                h = jnp.where(rmask, h * amp, h)
                keep = keep | rmask
            # compact the kept rows into the static fit matrix (order-
            # preserving, so int8 histogram sums stay bit-stable); the
            # full matrix becomes aux[0] purely for final leaf assignment
            idx_fit, goss_rows = compact_indices(keep, R_fit)
            valid_fit = jnp.arange(R_fit, dtype=jnp.int32) < goss_rows
            aux = (bins_t,) + tuple(aux)
            bins_t = jnp.take(bins_t, idx_fit, axis=1)
            g = jnp.where(valid_fit, jnp.take(g, idx_fit), 0.0)
            h = jnp.where(valid_fit, jnp.take(h, idx_fit), 0.0)
            include = valid_fit

        n = bins_t.shape[1]
        pos = jnp.zeros((n,), jnp.int32)
        aux_pos = tuple(jnp.zeros((bt.shape[1],), jnp.int32) for bt in aux)
        if goss_rows is None:
            goss_rows = jnp.sum(include, dtype=jnp.float32)

        rungs = spec.rungs(n)  # ascending [(R, impl)]
        if rungs:
            # row-major copies for the per-wave row gather, one per rung
            # implementation in use (shard-local under shard_map;
            # materialized once per tree): the fused kernel's lane-padded
            # int32 table, and ~n*F bytes at u8 for the XLA gather
            impls = {impl for _, impl in rungs}
            if "fused" in impls:
                rows_fused = gather_table(bins_t)
            if "xla" in impls:
                rows_xla = jnp.transpose(bins_t)
                if spec.B <= 256:
                    rows_xla = rows_xla.astype(jnp.uint8)

        # tile once per tree: the Pallas kernels want (F, nblk, 1, bm); done
        # inside the wave loop XLA re-materializes the tiled copy EVERY wave
        # (~10 ms x 20 waves per tree at 10M rows, seen in xprof)
        # (the routing kernel reads the same tiles; where it does not hold
        # the width a wave is routed on the untiled matrices, the test rows
        # are not tiled at all, and one-byte tiles are packed)
        if spec.kernels == "pallas":
            bins_k = tile_bins(bins_t, spec.bm, pack=spec.packed)
        else:
            bins_k = bins_t
        if spec.route == "pallas":
            bins_r = bins_k
            aux_k = tuple(tile_bins(bt, spec.bm) for bt in aux)
        else:
            bins_r = bins_t
            aux_k = aux

        if spec.precision == "int8":
            # per-tree symmetric int8 quantization of the (weighted) grads;
            # one-hot selection and counts stay exact, G/H sums carry a
            # bounded ~|g|max/(2*qmax)-per-sample rounding error in exchange
            # for the int8 MXU path. qmax shrinks above ~16.9M rows so the
            # worst-case i32 column accumulation (qmax * n_global) cannot
            # overflow — sharded, the i32 psum_scatter spans all shards.
            n_global = n * max(n_shards, 1)
            qmax = float(min(127, (2**31 - 1) // max(n_global, 1)))
            gmax = jnp.max(jnp.abs(g))
            hmax = jnp.max(jnp.abs(h))
            if n_shards > 1:
                # one global scale pair so quantized partials sum exactly
                from ..parallel.collectives import pmax

                gmax = pmax(gmax, axis)
                hmax = pmax(hmax, axis)
            sg = qmax / jnp.maximum(gmax, 1e-12)
            sh = qmax / jnp.maximum(hmax, 1e-12)
            gq = jnp.clip(jnp.round(g * sg), -qmax, qmax)  # f32 integers:
            hq = jnp.clip(jnp.round(h * sh), -qmax, qmax)  # kernel casts to i8
            inv = jnp.stack([1.0 / sg, 1.0 / sh, jnp.asarray(1.0)])
            G_, H_ = gq, hq

            def hist_finish(partial_h):  # (N, F, B, 3) i32 partial
                summed = combine_hist(partial_h)  # (N, F_loc, B, 3) global
                return summed.astype(jnp.float32) * inv[None, None, None, :]

        else:
            G_, H_ = g, h
            hist_finish = combine_hist

        def hist_partial(bins_in, pos_v, g_v, h_v, ids):
            return hist_wave(
                bins_in, pos_v, g_v, h_v, ids, B,
                bm=spec.bm, precision=spec.precision, kernels=spec.kernels,
                interpret=spec.fused_interpret,
            )

        def hist_call(pos_fit, ids):
            """Full-scan histogram (root + slow start + big-wave phases)."""
            return hist_finish(hist_partial(bins_k, pos_fit, G_, H_, ids))

        def hist_start(pos_fit, ids):
            """The root's and the slow start's full scans: the narrow waves,
            where hist.onehot_split factors the kernel's bin one-hot. A
            second naming beside the scopes, as `gbdt.hist.part` is."""
            with subscope("gbdt.hist.start"):
                return hist_call(pos_fit, ids)

        def hist_budget(R: int, impl: str = "xla"):
            """Leaf-partitioned histogram at static budget R: compact the
            rows belonging to the wave's nodes and histogram only those —
            R rows instead of n. The phase loop's condition guarantees the
            wave needs <= R rows. (This is deliberately cond-free: lax.cond
            around a Mosaic kernel takes >10 min to compile on this
            toolchain — phase-separated while_loops select the budget.)

            impl="fused": the row-index list goes straight into the fused
            Pallas kernel (per-row DMA gather + in-kernel accumulation) —
            no (R, F) XLA gather, no transpose. impl="xla": the original
            explicit gather + full-scan kernel (large budgets)."""

            def call(pos_fit, ids):
                # a second naming beside the scopes: the pass's kernel stays
                # under `gbdt.hist`, where hist_wave puts it
                with subscope("gbdt.hist.part"):
                    mask = jnp.zeros(pos_fit.shape, bool)
                    for k in range(int(ids.shape[0])):  # static width unroll
                        mask = mask | (pos_fit == ids[k])
                    idx, cnt = compact_indices(mask, R)
                    valid = jnp.arange(R, dtype=jnp.int32) < cnt
                    pg = jnp.where(valid, jnp.take(pos_fit, idx), -1)
                    gg = jnp.take(G_, idx)
                    hg = jnp.take(H_, idx)
                    if impl == "fused":
                        part = hist_wave_gather(
                            rows_fused, idx, pg, gg, hg, ids, F, B,
                            precision=spec.precision, kernels=spec.kernels,
                            bm_g=spec.bm_g, interpret=spec.fused_interpret,
                        )
                        return hist_finish(part)
                    bg = jnp.take(rows_xla, idx, axis=0)  # (R, F) u8
                    if spec.packed:
                        bt = tile_bins(jnp.transpose(bg), spec.bm, pack=True)
                    else:
                        bt = jnp.transpose(bg).astype(jnp.int32)
                        if spec.kernels == "pallas":
                            bt = bt.reshape(F, R // spec.bm, 1, spec.bm)
                    return hist_finish(hist_partial(bt, pg, gg, hg, ids))

            return call

        tr = TreeArrays(
            feat=jnp.full((M,), -1, jnp.int32),
            slot=jnp.zeros((M,), jnp.int32),
            slot_r=jnp.zeros((M,), jnp.int32),
            left=jnp.full((M,), -1, jnp.int32),
            right=jnp.full((M,), -1, jnp.int32),
            leaf=jnp.zeros((M,), jnp.float32),
            gain=jnp.zeros((M,), jnp.float32),
            hess=jnp.zeros((M,), jnp.float32),
            cnt=jnp.zeros((M,), jnp.float32),
            depth=jnp.zeros((M,), jnp.int32),
            n_nodes=jnp.asarray(1, jnp.int32),
        )

        # root histogram + stats + frontier. Sharded: hist0 is the owned
        # F-slice of the GLOBAL histogram, so any owned feature's bin-sum
        # (even an all-padding feature: every sample lands in bin 0) gives
        # the node totals — but each device sums a DIFFERENT feature's
        # column, so f32 rounding could diverge by a ULP across devices;
        # broadcast rank0's value so the "replicated" root stats really
        # are bit-identical (out_specs P() + check_vma=False would
        # otherwise silently ship device 0's copy while in-program scores
        # used per-device ones).
        ids0 = jnp.asarray([0], jnp.int32)  # root wave: one real slot
        pos_fit = jnp.where(include, pos, -1)
        hist0 = hist_start(pos_fit, ids0)  # (1, F_loc, B, 3)
        root_ghc = jnp.sum(hist0[0, 0], axis=0)  # feature 0 bin-sum = totals
        if n_shards > 1:
            from ..parallel.collectives import psum

            root_ghc = psum(
                jnp.where(jax.lax.axis_index(axis) == 0, root_ghc, 0.0), axis
            )
        tr = tr._replace(
            hess=tr.hess.at[0].set(root_ghc[1]),
            cnt=tr.cnt.at[0].set(root_ghc[2]),
            leaf=tr.leaf.at[0].set(node_value(root_ghc[0], root_ghc[1]) * spec.lr),
        )
        pool = jnp.zeros((M, F_loc, B, 3), jnp.float32)
        pool = pool.at[0].set(hist0[0])

        out0 = best_splits(hist0[:1], feat_mask, ranges_loc)
        f32 = jnp.float32
        fr = _Frontier(
            chg=jnp.full((M,), -jnp.inf, f32).at[0].set(out0[0][0]),
            flat=jnp.zeros((M,), jnp.int32).at[0].set(out0[1][0]),
            slotl=jnp.zeros((M,), jnp.int32).at[0].set(out0[2][0]),
            GL=jnp.zeros((M,), f32).at[0].set(out0[3][0]),
            HL=jnp.zeros((M,), f32).at[0].set(out0[4][0]),
            CL=jnp.zeros((M,), f32).at[0].set(out0[5][0]),
            GR=jnp.zeros((M,), f32).at[0].set(out0[6][0]),
            HR=jnp.zeros((M,), f32).at[0].set(out0[7][0]),
            CR=jnp.zeros((M,), f32).at[0].set(out0[8][0]),
            active=jnp.zeros((M,), bool).at[0].set(True),
        )
        leaves0 = jnp.asarray(1, jnp.int32)

        # wave log: [rows_scanned (static hist cost), rows_needed (exact
        # smaller-child sum), splits made, hist width N, rows_sampled
        # (GOSS-kept rows; included rows when GOSS is off)] per wave — the
        # roofline/ablation record (fetched once per tree, a few KB).
        # Row 0 is the root histogram pass. ALL row counts are PER-SHARD
        # (rows_scanned is the local n / local budget R already; the need
        # columns divide the globally-merged frontier counts by the shard
        # count) so scanned-vs-needed comparisons and per-chip utilization
        # stay unit-consistent on a mesh. Exact on one device.
        MW = wave_log_rows(M)  # waves <= splits + slow-start ramp + root
        inv_shards = 1.0 / float(max(n_shards, 1))
        goss_rows_f = goss_rows.astype(jnp.float32)
        if n_shards > 1:
            # the wave log ships replicated (out_specs P()): per-shard kept
            # counts can differ, so col 4 carries the cross-shard MEAN —
            # the same per-shard units as the other row columns
            from ..parallel.collectives import psum as _psum

            goss_rows_f = _psum(goss_rows_f, axis) * inv_shards
        wlog0 = jnp.zeros((MW, 5), jnp.float32)
        wlog0 = wlog0.at[0].set(
            jnp.stack([
                jnp.float32(n), root_ghc[2] * inv_shards,
                jnp.float32(0.0), jnp.float32(1.0), goss_rows_f,
            ])
        )
        wcnt0 = jnp.asarray(1, jnp.int32)

        def cond(state):
            tr, fr, pool, pos, aux_pos, leaves, wlog, wcnt = state
            return jnp.any(can_split(fr, tr, leaves))

        def wave_need(state):
            """Exact row count the NEXT wave's histograms touch: the sum of
            smaller-child counts over the nodes the selection would pick.
            Drives the phase-loop budget transitions (computed from frontier
            stats — C-channel counts match the compaction mask exactly)."""
            tr, fr, pool, pos, aux_pos, leaves, wlog, wcnt = state
            ok = can_split(fr, tr, leaves)
            sel, sel_ok = select(ok, fr, tr, NW)
            order_cum = jnp.cumsum(sel_ok.astype(jnp.int32), dtype=jnp.int32)
            sel_ok &= (leaves + order_cum) <= spec.leaf_cap
            small_cnt = jnp.minimum(fr.CL[sel], fr.CR[sel])
            return jnp.sum(jnp.where(sel_ok, small_cnt, 0.0))

        def make_body(nw: int, hist_fn=None, hist_rows: int = None):
            return lambda state: wave_body(state, nw, hist_fn, hist_rows)

        def wave_body(state, nw: int, hist_fn=None, hist_rows: int = None):
            tr, fr, pool, pos, aux_pos, leaves, wlog, wcnt = state
            ok = can_split(fr, tr, leaves)
            sel, sel_ok = select(ok, fr, tr, nw)

            # leaf budget count-off in selection order (level: node order
            # within the level; loss: gain order) — reference semantics
            order_cum = jnp.cumsum(sel_ok.astype(jnp.int32), dtype=jnp.int32)
            sel_ok &= (leaves + order_cum) <= spec.leaf_cap
            k_cnt = jnp.sum(sel_ok, dtype=jnp.int32)

            # children allocation in selection order
            prefix = jnp.cumsum(
                sel_ok.astype(jnp.int32), dtype=jnp.int32
            ) - sel_ok.astype(jnp.int32)
            lch = tr.n_nodes + 2 * prefix
            rch = lch + 1
            nid = sel
            scatter_id = jnp.where(sel_ok, nid, M)  # M = dropped
            lch_id = jnp.where(sel_ok, lch, M)
            rch_id = jnp.where(sel_ok, rch, M)

            f_best = fr.flat[nid] // B
            slot_r = fr.flat[nid] % B
            slot_l = fr.slotl[nid]
            if rlo_g is not None:
                # EFB member range of the chosen boundary slot (global
                # tables: f_best is a global column id) — bounds routing
                # so other bundle members' rows stay on the default side
                sel_lo = rlo_g[f_best, slot_r]
                sel_hi = rhi_g[f_best, slot_r]
            else:
                sel_lo = jnp.zeros_like(f_best)
                sel_hi = jnp.full_like(f_best, B - 1)
            GLs, HLs, CLs = fr.GL[nid], fr.HL[nid], fr.CL[nid]
            GRs, HRs, CRs = fr.GR[nid], fr.HR[nid], fr.CR[nid]
            child_depth = tr.depth[nid] + 1

            drop = dict(mode="drop")
            tr = tr._replace(
                feat=tr.feat.at[scatter_id].set(f_best, **drop),
                slot=tr.slot.at[scatter_id].set(slot_l, **drop),
                slot_r=tr.slot_r.at[scatter_id].set(slot_r, **drop),
                left=tr.left.at[scatter_id].set(lch, **drop),
                right=tr.right.at[scatter_id].set(rch, **drop),
                gain=tr.gain.at[scatter_id].set(fr.chg[nid], **drop),
                leaf=tr.leaf.at[lch_id]
                .set(node_value(GLs, HLs) * spec.lr, **drop)
                .at[rch_id]
                .set(node_value(GRs, HRs) * spec.lr, **drop),
                hess=tr.hess.at[lch_id].set(HLs, **drop).at[rch_id].set(HRs, **drop),
                cnt=tr.cnt.at[lch_id].set(CLs, **drop).at[rch_id].set(CRs, **drop),
                depth=tr.depth.at[lch_id]
                .set(child_depth, **drop)
                .at[rch_id]
                .set(child_depth, **drop),
                n_nodes=(tr.n_nodes + 2 * k_cnt).astype(jnp.int32),
            )

            # routing (train + any aux sets)
            with scope("gbdt.route"):
                route = partial(
                    route_wave, kernels=spec.route, bm=spec.bm,
                    interpret=spec.fused_interpret,
                )
                pos = route(
                    bins_r, pos, sel_ok, nid, f_best, slot_l, lch, rch,
                    sel_lo, sel_hi,
                )
                aux_pos = tuple(
                    route(
                        bt, ap, sel_ok, nid, f_best, slot_l, lch, rch,
                        sel_lo, sel_hi,
                    )
                    for bt, ap in zip(aux_k, aux_pos)
                )

            # smaller-child histogram + sibling subtraction
            small = jnp.where(CLs <= CRs, lch, rch)
            big = jnp.where(CLs <= CRs, rch, lch)
            ids = jnp.where(sel_ok, small, -2)
            pos_fit = jnp.where(include, pos, -1)
            h_small = (hist_fn or hist_call)(pos_fit, ids)
            parent_h = pool[nid]
            h_big = parent_h - h_small
            pool = pool.at[jnp.where(sel_ok, small, M)].set(h_small, **drop)
            pool = pool.at[jnp.where(sel_ok, big, M)].set(h_big, **drop)

            # frontier refresh for the 2*NW children
            child_ids = jnp.concatenate([small, big])
            child_ok = jnp.concatenate([sel_ok, sel_ok])
            hists = jnp.concatenate([h_small, h_big], axis=0)
            out = best_splits(hists, feat_mask, ranges_loc)
            cids = jnp.where(child_ok, child_ids, M)
            fr = _Frontier(
                chg=fr.chg.at[scatter_id].set(-jnp.inf, **drop).at[cids].set(out[0], **drop),
                flat=fr.flat.at[cids].set(out[1], **drop),
                slotl=fr.slotl.at[cids].set(out[2], **drop),
                GL=fr.GL.at[cids].set(out[3], **drop),
                HL=fr.HL.at[cids].set(out[4], **drop),
                CL=fr.CL.at[cids].set(out[5], **drop),
                GR=fr.GR.at[cids].set(out[6], **drop),
                HR=fr.HR.at[cids].set(out[7], **drop),
                CR=fr.CR.at[cids].set(out[8], **drop),
                active=fr.active.at[scatter_id]
                .set(False, **drop)
                .at[cids]
                .set(True, **drop),
            )
            need = jnp.sum(
                jnp.where(sel_ok, jnp.minimum(CLs, CRs), 0.0)
            ) * inv_shards
            rows_f = jnp.float32(n if hist_rows is None else hist_rows)
            wlog = wlog.at[wcnt].set(
                jnp.stack([
                    rows_f, need, k_cnt.astype(jnp.float32), jnp.float32(nw),
                    goss_rows_f,
                ]),
                mode="drop",
            )
            return (
                tr, fr, pool, pos, aux_pos,
                (leaves + k_cnt).astype(jnp.int32),
                wlog, (wcnt + 1).astype(jnp.int32),
            )

        state = (tr, fr, pool, pos, aux_pos, leaves0, wlog0, wcnt0)
        # slow start: after k waves at most 2^k nodes are expandable, so the
        # first waves run right-sized (N = 1, 2, 4, ...) — identical split
        # decisions to full-width waves at a fraction of the one-hot matmul
        # rows. What that buys is measured, not proportional: below 32 nodes
        # the kernel factors the bin one-hot (hist.onehot_split), whose whole
        # build, rows x F x B compares, would cost a pass the same at every
        # width; each width's time on the v5e is in PERF.md section 5
        nw_ss = 1
        while nw_ss < NW:
            state = wave_body(state, nw_ss, hist_start)
            nw_ss *= 2

        if rungs:
            # phase-separated growth: full scans while waves are big, then
            # tighter partitioned budgets as the frontier's row need
            # shrinks, then a full-scan tail for any non-monotone leftovers
            # (need is near-monotone decreasing under gain-ordered
            # selection; the tail keeps pathological orders correct)
            Rs = sorted(rungs, reverse=True)  # big -> small [(R, impl)]

            def mk_cond(lo, hi):
                # `need` is the GLOBAL wave row count (frontier stats are
                # merged/replicated across shards) compared against the
                # LOCAL budget R: global need <= local budget implies every
                # shard's local rows fit — conservative under a mesh (a
                # shard transitions ~D x later than its own load requires)
                # but never drops rows, and exact on one device.
                def cond_fn(state):
                    c = cond(state)
                    need = wave_need(state)
                    if hi is not None:
                        c &= need <= hi
                    if lo is not None:
                        c &= need > lo
                    return c

                return cond_fn

            state = jax.lax.while_loop(
                mk_cond(Rs[0][0], None), make_body(NW), state
            )
            for i, (R, impl) in enumerate(Rs):
                nxt = Rs[i + 1][0] if i + 1 < len(Rs) else None
                state = jax.lax.while_loop(
                    mk_cond(nxt, R),
                    make_body(NW, hist_budget(R, impl), hist_rows=R),
                    state,
                )
            state = jax.lax.while_loop(cond, make_body(NW), state)
        else:
            state = jax.lax.while_loop(cond, make_body(NW), state)
        tr, fr, pool, pos, aux_pos, leaves, wlog, wcnt = state
        return tr, pos, aux_pos, wlog

    return grow
