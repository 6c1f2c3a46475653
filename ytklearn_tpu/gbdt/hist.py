"""Histogram accumulation kernels — the GBDT hot op, TPU-first.

The reference's hottest loop (HistogramBuilder.java:72-90) scatter-adds
(g, h, 1) into per-(node, feature, bin) slots. XLA scatter serializes on
TPU (measured ~1.7 s per 1M-row pass), so the TPU path instead computes
the histogram as a blocked one-hot matmul on the MXU:

    for each (feature-group, sample-block) grid step:
        P  (N, bm)  = node one-hot                  # VPU, once per block
        PV (3N, bm) = [P*g | P*h | P]               # VPU, once per block
        for f in group:                             # unrolled F_g times
            OH (B, bm)   = bin one-hot              # VPU compare vs iota
            out[f] (3N,B) += PV @ OH.T              # MXU NT-dot, f32 accum

A narrow wave factors the bin one-hot (`onehot_split`: H > 1). A bin is
b = hi*L + lo with H*L = B, and for each feature the kernel builds

            OH_hi (H, bm), OH_lo (L, bm)            # VPU, H + L compares
            Q (3N*H, bm) = PV[:, None] * OH_hi[None] # VPU, 3N*H multiplies
            out[f] (3N*H, L) += Q @ OH_lo.T         # MXU NT-dot, f32 accum

so a row costs H + L compares and 3N*H multiplies by 0 or 1 where the
whole one-hot costs B compares whatever N is. The MACs, the operands'
values and the f32 sums are the same. Element (x*H + hi, lo) of the
output lies at (x*H + hi)*L + lo = x*B + hi*L + lo in row-major order,
where element (x, hi*L + lo) of (3N, B) lies, so (F, 3N*H, L) reshapes
to (F, 3N, B) without moving data.

Layouts are lane-major throughout (P (N, bm), OH (B, bm), samples always
on lanes) so no in-kernel transposes occur and no (x, 1) blocks blow up
VMEM with lane padding. Grouping features inside one grid step amortizes
the node one-hot (a 28x saving at wide waves) and the pos/g/h DMAs.
Samples whose pos is not in `node_ids` (including pos = -1 dead rows)
match no one-hot row and vanish.

bf16 operands halve MXU time; histogram sums accumulate in f32 either
way (counts stay exact — 0/1 one-hots are exact in bf16). precision="f32"
forces true-f32 MXU passes (Precision.HIGHEST — TPU silently runs f32
dots at bf16 input precision otherwise); "int8" takes pre-quantized
grads and accumulates in i32.

A dense-einsum twin provides the same math where Mosaic kernels can't
compile (tests run on the virtual mesh with JAX_PLATFORMS=cpu). Which
family runs is the caller's `kernels` ("pallas" | "dense"), resolved once
from the platform (GBDTTrainer._grow_spec); nothing here asks it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import scope
from .binning import feature_chunk


# sample-block width: the Pallas grid's lane-major tile. 16384 measured
# ~13% faster than 8192 at the Higgs shape (fewer grid steps amortize the
# per-step P/PV build and DMA; scripts/tune_hist_kernel.py)
BM_DEFAULT = 16384


def _pad_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def tile_bins(bins_t, bm: int, pack: bool = False):
    """(F, n) bin matrix -> (F, n/bm, 1, bm), the layout the full-scan and
    routing kernels block over. 32-bit bins reshape for free. A uint8 matrix
    is widened for the reshape and narrowed after it, behind an optimization
    barrier so XLA cannot fold the pair away: its direct uint8 reshape into
    a shape with a size-1 sublane dim compiles in time proportional to n
    (139 s at 4.2M rows, most of the round program's ~9 min at 10.5M; with
    the barrier 12 s at 4.2M — TPU v5 lite, libtpu 0.0.34).

    pack (one-byte bins the routing kernel does not read: GrowSpec.packed):
    (F, n/bm, 1, bm/4) int32, a word the bins of four rows of its block,
    byte k of word j the block's row k * bm/4 + j, which `gbdt_hist_scan[_q]`
    take apart with three shifts into the block's rows in order. A uint8
    tile with a size-1 sublane dim takes 4 bytes a bin on the device (3.05
    GiB at 2,000 x 409,600), the words one. The widened copy the words are
    made from is binning's 4-byte-a-cell copy again, so it keeps to that
    budget: past WHOLE_BYTES the words are made `feature_chunk` features at
    a time, one part after the other (a loop, so no two parts' copies are
    alive together)."""
    F, n = bins_t.shape
    if not pack:
        if bins_t.dtype.itemsize >= 4:
            return bins_t.reshape(F, n // bm, 1, bm)
        wide = bins_t.astype(jnp.int32).reshape(F, n // bm, 1, bm)
        return jax.lax.optimization_barrier(wide).astype(bins_t.dtype)
    assert bins_t.dtype.itemsize == 1 and bm % 4 == 0, (bins_t.dtype, bm)
    nblk, q = n // bm, bm // 4

    def words(part):
        f = part.shape[0]
        w = jax.lax.optimization_barrier(part.astype(jnp.int32))
        w = w.reshape(f, nblk, 4, q)
        w = w[:, :, 0] | (w[:, :, 1] << 8) | (w[:, :, 2] << 16) | (w[:, :, 3] << 24)
        return w.reshape(f, nblk, 1, q)

    c = feature_chunk(F, n)
    if c >= F:
        return words(bins_t)

    def body(i, out):
        # the last part starts early rather than run short: same words twice
        lo = jnp.minimum(i * c, F - c)
        part = jax.lax.dynamic_slice(bins_t, (lo, 0), (c, n))
        return jax.lax.dynamic_update_slice(out, words(part), (lo, 0, 0, 0))

    return jax.lax.fori_loop(
        0, -(-F // c), body, jnp.zeros((F, nblk, 1, q), jnp.int32)
    )


def _block_bins(bins_ref, fi: int, packed: bool):
    """One feature's bins of a row block as (1, bm) int32 lanes, from a
    (1, bm) block of bins or a (1, bm/4) block of tile_bins' packed words."""
    v = bins_ref[fi, 0, 0, :][None, :]
    if not packed:
        return v.astype(jnp.int32)
    return jnp.concatenate(
        [v & 255, (v >> 8) & 255, (v >> 16) & 255, (v >> 24) & 255], axis=1
    )


def onehot_split(N: int, B: int) -> int:
    """H, the factor of the full-scan kernel's bin one-hot at N nodes a
    wave and B bins (module docstring): the power of two of least cost a
    row and feature, which is the larger of the one-hot build's compares
    (B at H = 1, else H + B/H) and the MXU pass's 3N*H rows at two
    compares' time a row, the weight that per-N kernel times on the v5e
    gave at both GBDT cells' shapes (PERF.md section 6); 1 where B is no
    power of two. At B = 256: N 1 -> 8; 2, 4 -> 4; 8, 16 -> 2; from 32 -> 1."""
    if B & (B - 1):
        return 1

    def cost(H: int) -> int:
        return max(B if H == 1 else H + B // H, 2 * 3 * N * H)

    best, H = 1, 2
    while H < B:
        if cost(H) < cost(best):
            best = H
        H *= 2
    return best


@partial(jax.jit, static_argnames=("B", "bm", "fg", "use_bf16", "H", "interpret"))
def _hist_pallas(
    bins4, pos, g, h, node_ids, B: int, bm: int, fg: int, use_bf16: bool,
    H: int = 1, interpret: bool = False,
):
    """(F, 3N, B) f32 histograms, rows [g*N | h*N | c*N]; H > 1 factors
    the bin one-hot (hist_wave passes onehot_split(N, B))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, nblk, bw = bins4.shape[0], bins4.shape[1], bins4.shape[3]
    packed = bw != bm  # tile_bins' words: four rows' bins an int32
    n = nblk * bm
    N = node_ids.shape[0]
    assert F % fg == 0, (F, fg)
    assert B % H == 0, (B, H)
    L = B // H
    shift = L.bit_length() - 1  # H > 1: B and L are powers of two
    cdt = jnp.bfloat16 if use_bf16 else jnp.float32
    prec = None if use_bf16 else jax.lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))  # A @ B.T

    pos3 = pos.reshape(nblk, 1, bm)
    g3 = g.reshape(nblk, 1, bm)
    h3 = h.reshape(nblk, 1, bm)
    # H > 1: each node's id H times, so that row x*H + hi of PV is row x of
    # the unfactored PV and Q is PV times OH_hi tiled 3N times, with no 3-D
    # (3N, H, bm) product, whose size-H sublane dim Mosaic pads to a tile
    ids2 = (node_ids if H == 1 else jnp.repeat(node_ids, H)).reshape(N * H, 1)

    def kernel(bins_ref, pos_ref, g_ref, h_ref, ids_ref, out_ref):
        blk = pl.program_id(1)
        p = pos_ref[0, 0, :][None, :]  # (1, bm) lanes
        P = (ids_ref[:, 0:1] == p).astype(cdt)  # (N*H, bm)
        gv = g_ref[0, 0, :][None, :].astype(cdt)
        hv = h_ref[0, 0, :][None, :].astype(cdt)
        PV = jnp.concatenate([P * gv, P * hv, P], axis=0)  # (3N*H, bm)
        if H == 1:
            iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
        else:
            iota_h = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
            iota_l = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
        for fi in range(fg):
            b = _block_bins(bins_ref, fi, packed)  # (1, bm)
            if H == 1:
                OH = (iota_b == b).astype(cdt)  # (B, bm)
                acc = jax.lax.dot_general(
                    PV, OH, nt, precision=prec,
                    preferred_element_type=jnp.float32,
                )  # (3N, B)
            else:
                OH_hi = (iota_h == (b >> shift)).astype(cdt)  # (H, bm)
                OH_lo = (iota_l == (b & (L - 1))).astype(cdt)  # (L, bm)
                Q = PV * pltpu.repeat(OH_hi, 3 * N, axis=0)  # (3N*H, bm)
                acc = jax.lax.dot_general(
                    Q, OH_lo, nt, precision=prec,
                    preferred_element_type=jnp.float32,
                )  # (3N*H, L)

            @pl.when(blk == 0)
            def _():
                out_ref[fi, :, :] = acc

            @pl.when(blk > 0)
            def _():
                out_ref[fi, :, :] = out_ref[fi, :, :] + acc

    out = pl.pallas_call(
        kernel,
        name="gbdt_hist_scan",
        grid=(F // fg, nblk),
        in_specs=[
            pl.BlockSpec((fg, 1, 1, bw), lambda fo, k: (fo, k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((N * H, 1), lambda fo, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((fg, 3 * N * H, L), lambda fo, k: (fo, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, 3 * N * H, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bins4, pos3, g3, h3, ids2)
    return out.reshape(F, 3 * N, B)  # rows [g*N | h*N | c*N]


@partial(jax.jit, static_argnames=("B", "bm", "fg", "interpret"))
def _hist_pallas_q(
    bins4, pos, gq, hq, node_ids, B: int, bm: int, fg: int,
    interpret: bool = False,
):
    """int8 variant: gq/hq are pre-quantized grads as f32 integers in
    [-127, 127] (caller owns the scales); one-hots are exact, dots run at
    2x MXU rate with i32 accumulation (|sum| <= bm*127 per tile, far from
    overflow). Counts stay exact. Output (F, 3N, B) int32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, nblk, bw = bins4.shape[0], bins4.shape[1], bins4.shape[3]
    packed = bw != bm
    N = node_ids.shape[0]
    assert F % fg == 0, (F, fg)
    nt = (((1,), (1,)), ((), ()))  # A @ B.T

    pos3 = pos.reshape(nblk, 1, bm)
    g3 = gq.reshape(nblk, 1, bm)
    h3 = hq.reshape(nblk, 1, bm)
    ids2 = node_ids.reshape(N, 1)

    def kernel(bins_ref, pos_ref, g_ref, h_ref, ids_ref, out_ref):
        blk = pl.program_id(1)
        p = pos_ref[0, 0, :][None, :]
        Pb = ids_ref[:, 0:1] == p  # (N, bm) bool
        # Mosaic legalizes neither int8 multiplies nor int8/i1 selects, so
        # the masking runs in f32 (inputs are pre-rounded to [-127, 127])
        # and the assembled block casts to int8 for the 2x-rate dot
        P = Pb.astype(jnp.float32)
        gv = P * g_ref[0, 0, :][None, :]
        hv = P * h_ref[0, 0, :][None, :]
        PV = jnp.concatenate([gv, hv, P], axis=0).astype(jnp.int8)  # (3N, bm)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
        for fi in range(fg):
            b = _block_bins(bins_ref, fi, packed)
            OH = (iota_b == b).astype(jnp.int8)  # (B, bm)
            acc = jax.lax.dot_general(
                PV, OH, nt, preferred_element_type=jnp.int32
            )  # (3N, B) i32

            @pl.when(blk == 0)
            def _():
                out_ref[fi, :, :] = acc

            @pl.when(blk > 0)
            def _():
                out_ref[fi, :, :] = out_ref[fi, :, :] + acc

    return pl.pallas_call(
        kernel,
        name="gbdt_hist_scan_q",
        grid=(F // fg, nblk),
        in_specs=[
            pl.BlockSpec((fg, 1, 1, bw), lambda fo, k: (fo, k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
            pl.BlockSpec((N, 1), lambda fo, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((fg, 3 * N, B), lambda fo, k: (fo, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, 3 * N, B), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bins4, pos3, g3, h3, ids2)


@partial(jax.jit, static_argnames=("B",))
def _hist_dense_q(bins_t, pos, gq, hq, node_ids, B: int):
    """int8 math via int32 einsum (CPU / fallback path for the q kernel);
    gq/hq are f32 integers in [-127, 127]."""
    P = (node_ids[:, None] == pos[None, :]).astype(jnp.int32)
    OH = (
        bins_t.astype(jnp.int32)[:, None, :] == jnp.arange(B)[None, :, None]
    ).astype(jnp.int32)
    gi = gq.astype(jnp.int32)
    hi = hq.astype(jnp.int32)
    hg = jnp.einsum("xn,fbn->fxb", P * gi[None, :], OH)
    hh = jnp.einsum("xn,fbn->fxb", P * hi[None, :], OH)
    hc = jnp.einsum("xn,fbn->fxb", P, OH)
    return jnp.concatenate([hg, hh, hc], axis=1)  # (F, 3N, B) i32


@partial(jax.jit, static_argnames=("B", "use_bf16"))
def _hist_dense(bins_t, pos, g, h, node_ids, B: int, use_bf16: bool):
    """Same math as the Pallas kernel via einsum (CPU / fallback path)."""
    cdt = jnp.bfloat16 if use_bf16 else jnp.float32
    P = (node_ids[:, None] == pos[None, :]).astype(cdt)  # (N, n)
    OH = (
        bins_t.astype(jnp.int32)[:, None, :] == jnp.arange(B)[None, :, None]
    ).astype(cdt)  # (F, B, n)
    gv = g.astype(cdt)
    hv = h.astype(cdt)
    hg = jnp.einsum("xn,fbn->fxb", P * gv[None, :], OH, preferred_element_type=jnp.float32)
    hh = jnp.einsum("xn,fbn->fxb", P * hv[None, :], OH, preferred_element_type=jnp.float32)
    hc = jnp.einsum("xn,fbn->fxb", P, OH, preferred_element_type=jnp.float32)
    return jnp.concatenate([hg, hh, hc], axis=1)  # (F, 3N, B)


def _hist_dense_at(precision: str, bins_t, pos, g, h, node_ids, B: int):
    if precision == "int8":
        return _hist_dense_q(bins_t, pos, g, h, node_ids, B)
    return _hist_dense(bins_t, pos, g, h, node_ids, B, precision == "bf16")


def _pick_fg(F: int) -> int:
    """Features a grid step of the full-scan kernel: the widest of the
    listed group widths that divides F. A wider group amortizes the
    per-step P/PV build and the pos/g/h DMAs over more features; the output
    block it keeps in VMEM is fg x 3N x B floats (2.75 MB at 14 x 192 x
    256). The order of the list dates from a retired set-up and has not
    been re-measured on the v5e; what each width runs today is in PERF.md
    section 5 (28 columns: 14, a grid of 2 x 641 steps; 2,000 columns: 8,
    250 x 25 steps; a prime count such as 137: 1). Retuning is ROADMAP A7."""
    for fg in (14, 7, 8, 4, 5, 6, 3, 2):
        if F % fg == 0:
            return fg
    return 1


def hist_wave(
    bins_t,
    pos,
    g,
    h,
    node_ids,
    B: int,
    *,
    precision: str,
    kernels: str,
    bm: int = BM_DEFAULT,
    interpret: bool = False,
):
    """(N, F, B, 3) histograms for the nodes listed in `node_ids`.

    bins_t   (F, n) int32 — transposed bin matrix (n padded to bm), or
                            pre-tiled (F, n/bm, 1, bm), or tile_bins'
                            packed words (F, n/bm, 1, bm/4)
    pos      (n,) int32   — tree-node id per sample (-1 or absent = skip)
    g, h     (n,) f32     — weighted grad / hess per sample; at "int8"
                            f32 integers in [-127, 127] (caller's scales)
    node_ids (N,) int32   — node ids to histogram (-2 pads: match nothing)
    precision "bf16" | "f32" (f32 sums) | "int8" (exact int32 sums)
    kernels   "pallas" (Mosaic, the chip) | "dense" (einsum, elsewhere)
    interpret  the Pallas family through the Pallas interpreter (CPU tests)
    """
    F = bins_t.shape[0]
    N = node_ids.shape[0]
    with scope("gbdt.hist"):
        if kernels == "pallas":
            bins4 = bins_t if bins_t.ndim == 4 else tile_bins(bins_t, bm)
            if precision == "int8":
                out = _hist_pallas_q(
                    bins4, pos, g, h, node_ids, B, bm, _pick_fg(F),
                    interpret,
                )
            else:
                out = _hist_pallas(
                    bins4, pos, g, h, node_ids, B, bm, _pick_fg(F),
                    precision == "bf16", onehot_split(N, B), interpret,
                )
        else:
            bins2 = bins_t if bins_t.ndim == 2 else bins_t.reshape(F, -1)
            out = _hist_dense_at(precision, bins2, pos, g, h, node_ids, B)
        # (F, 3N, B) -> (N, F, B, 3)
        out = out.reshape(F, 3, N, B)
        return jnp.transpose(out, (2, 0, 3, 1))


# ---------------------------------------------------------------------------
# Fused compact+gather+histogram kernel (leaf-partitioned waves)
# ---------------------------------------------------------------------------
#
# Late-tree waves touch a few thousand rows out of millions. The XLA
# formulation (gather (R, F) rows + transpose + full kernel) loses on TPU
# because real-index gathers run far off the strided path. This kernel
# fuses the row gather INTO the histogram pass: the wave's compacted
# row-index list arrives in SMEM tiles, each grid step issues one small
# DMA per selected row (HBM row-major bins -> VMEM scratch, all in
# flight before the first wait), and the gathered tile feeds the same
# one-hot MXU accumulation as the dense kernels — no (R, F) gather, no
# transpose, no extra HBM round trip. Wave cost becomes O(R) DMA issues
# + O(R*N*B) MACs instead of O(n*N*B).
#
# Layout: the gathered tile is ROW-major (rows on sublanes), so the bin
# one-hot is built per feature from a lane-column slice and the MXU pass
# is a plain NN dot PV (3N, bm_g) @ OH (bm_g, bins B) — pos/g/h tiles stay
# lane-major exactly like the full-scan kernels.

BMG_DEFAULT = 1024  # gathered-tile rows (sublane dim of the NN dot)

# The gather source is a (n, W) int32 table, W = F padded to whole 128-lane
# tiles, because Mosaic (libtpu 0.0.34, v5e) refuses anything narrower for a
# one-row DMA: a uint8 (n, 28) matrix tiles (8,128)(4,1) — four rows packed
# per sublane word — and "Slice shape along dimension 0 must be aligned to
# tiling (8), but is 1"; an int32 (n, 28) matrix tiles (1,128) and "Slice
# shape along dimension 1 must be aligned to tiling (128), but is 28". The
# price is 512 B of HBM per row per 128 features (5.4 GB at 10.5M x 28,
# where the uint8 matrix is 0.29 GB); each DMA moves one 512 B tile row.
GATHER_LANES = 128

# What the fused kernel keeps in VMEM whatever the row count: its whole
# (F, 3N, B) output and the gathered (bm_g, W) tile; and it unrolls a step a
# feature. 5.5 MB + 0.5 MB at 28 features, 64 nodes, 256 bins; on the v5e
# (16 MiB of scoped VMEM) Mosaic refuses it from 80 features on at that
# wave. Past FUSED_VMEM_BYTES no rung is fused (GBDTTrainer._grow_spec).
FUSED_VMEM_BYTES = 12 << 20


def fused_holds(F: int, N: int, B: int, bm_g: int = BMG_DEFAULT) -> bool:
    """Whether `gbdt_hist_gather` holds F features at N nodes a wave."""
    held = F * 3 * N * B * 4 + bm_g * _pad_to(F, GATHER_LANES) * 4
    return held <= FUSED_VMEM_BYTES


def gather_table(bins_t):
    """(F, n) bin matrix -> the fused kernel's (n, W) int32 row table."""
    F = bins_t.shape[0]
    rows = jnp.transpose(bins_t).astype(jnp.int32)
    return jnp.pad(rows, ((0, 0), (0, _pad_to(F, GATHER_LANES) - F)))


def _gather_grid_call(
    rows, idx, pos_g, g_t, h_t, ids2, out_dtype, kernel, F, B, bm_g, interpret
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R = idx.shape[0]
    N = ids2.shape[0]
    assert R % bm_g == 0, (R, bm_g)
    return pl.pallas_call(
        kernel,
        name="gbdt_hist_gather",
        grid=(R // bm_g,),
        in_specs=[
            pl.BlockSpec((bm_g,), lambda t: (t,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # rows stay in HBM
            pl.BlockSpec((1, 1, bm_g), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, 1, bm_g), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, 1, bm_g), lambda t: (t, 0, 0)),
            pl.BlockSpec((N, 1), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((F, 3 * N, B), lambda t: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, 3 * N, B), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm_g, rows.shape[1]), rows.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(idx, rows, pos_g, g_t, h_t, ids2)


def _gather_rows_dma(idx_ref, rows_ref, scratch, sem, bm_g: int):
    """Issue one DMA per selected row (all in flight), then drain. The
    issue loop is the kernel's dominant cost at large R — which is why
    the budget ladder only routes small waves here."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def issue(i, c):
        iv = idx_ref[i]
        pltpu.make_async_copy(
            rows_ref.at[pl.ds(iv, 1), :], scratch.at[pl.ds(i, 1), :], sem
        ).start()
        return c

    jax.lax.fori_loop(0, bm_g, issue, 0)

    def drain(i, c):
        pltpu.make_async_copy(
            rows_ref.at[pl.ds(0, 1), :], scratch.at[pl.ds(0, 1), :], sem
        ).wait()
        return c

    jax.lax.fori_loop(0, bm_g, drain, 0)


@partial(
    jax.jit, static_argnames=("F", "B", "bm_g", "use_bf16", "interpret")
)
def _hist_gather_pallas(
    rows, idx, pos_g, g, h, node_ids, F: int, B: int, bm_g: int,
    use_bf16: bool, interpret: bool,
):
    """Fused gather+histogram, f32/bf16 MXU variant.

    rows     (n, W) i32    — gather_table(): ROW-major bins, HBM resident,
                             features in lanes [0, F)
    idx      (R,) i32      — compacted row indices (R % bm_g == 0; slots
                             past the wave's row count point at row 0 and
                             are masked by pos_g = -1)
    pos_g    (R,) i32      — node id per gathered row (-1 = dead slot)
    g, h     (R,) f32      — gathered weighted grad / hess
    node_ids (N,) i32      — wave node ids (-2 pads match nothing)
    Returns (F, 3N, B) f32 partial histograms, rows [g*N | h*N | c*N].
    """
    from jax import lax

    R = idx.shape[0]
    N = node_ids.shape[0]
    cdt = jnp.bfloat16 if use_bf16 else jnp.float32
    prec = None if use_bf16 else jax.lax.Precision.HIGHEST
    nn = (((1,), (0,)), ((), ()))  # A @ B

    pos3 = pos_g.reshape(R // bm_g, 1, bm_g)
    g3 = g.reshape(R // bm_g, 1, bm_g)
    h3 = h.reshape(R // bm_g, 1, bm_g)
    ids2 = node_ids.reshape(N, 1)

    def kernel(idx_ref, rows_ref, pos_ref, g_ref, h_ref, ids_ref, out_ref,
               scratch, sem):
        from jax.experimental import pallas as pl

        t = pl.program_id(0)
        _gather_rows_dma(idx_ref, rows_ref, scratch, sem, bm_g)
        p = pos_ref[0, 0, :][None, :]  # (1, bm_g) lanes
        P = (ids_ref[:, 0:1] == p).astype(cdt)  # (N, bm_g)
        gv = g_ref[0, 0, :][None, :].astype(cdt)
        hv = h_ref[0, 0, :][None, :].astype(cdt)
        PV = jnp.concatenate([P * gv, P * hv, P], axis=0)  # (3N, bm_g)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
        for f in range(F):
            col = scratch[:, f : f + 1]  # (bm_g, 1)
            OH = (col == iota_b).astype(cdt)  # (bm_g, B) row-major
            acc = lax.dot_general(
                PV, OH, nn, precision=prec,
                preferred_element_type=jnp.float32,
            )  # (3N, B)

            @pl.when(t == 0)
            def _():
                out_ref[f, :, :] = acc

            @pl.when(t > 0)
            def _():
                out_ref[f, :, :] = out_ref[f, :, :] + acc

    return _gather_grid_call(
        rows, idx, pos3, g3, h3, ids2, jnp.float32, kernel, F, B, bm_g,
        interpret,
    )


@partial(jax.jit, static_argnames=("F", "B", "bm_g", "interpret"))
def _hist_gather_pallas_q(
    rows, idx, pos_g, gq, hq, node_ids, F: int, B: int, bm_g: int,
    interpret: bool,
):
    """Fused gather+histogram, int8 variant (gq/hq are f32 integers in
    [-127, 127], caller owns the scales; i32 accumulation is exact and
    order-independent, so fused-budget trees equal full-scan trees
    bit-for-bit). Returns (F, 3N, B) int32."""
    from jax import lax

    R = idx.shape[0]
    N = node_ids.shape[0]
    nn = (((1,), (0,)), ((), ()))

    pos3 = pos_g.reshape(R // bm_g, 1, bm_g)
    g3 = gq.reshape(R // bm_g, 1, bm_g)
    h3 = hq.reshape(R // bm_g, 1, bm_g)
    ids2 = node_ids.reshape(N, 1)

    def kernel(idx_ref, rows_ref, pos_ref, g_ref, h_ref, ids_ref, out_ref,
               scratch, sem):
        from jax.experimental import pallas as pl

        t = pl.program_id(0)
        _gather_rows_dma(idx_ref, rows_ref, scratch, sem, bm_g)
        p = pos_ref[0, 0, :][None, :]
        Pb = ids_ref[:, 0:1] == p  # (N, bm_g) bool
        # int8 multiplies / selects don't legalize in Mosaic — mask in f32
        # and cast the assembled block (same trick as _hist_pallas_q)
        P = Pb.astype(jnp.float32)
        gv = P * g_ref[0, 0, :][None, :]
        hv = P * h_ref[0, 0, :][None, :]
        PV = jnp.concatenate([gv, hv, P], axis=0).astype(jnp.int8)  # (3N, bm_g)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
        for f in range(F):
            OH = (scratch[:, f : f + 1] == iota_b).astype(jnp.int8)  # (bm_g, B)
            acc = lax.dot_general(
                PV, OH, nn, preferred_element_type=jnp.int32
            )  # (3N, B) i32

            @pl.when(t == 0)
            def _():
                out_ref[f, :, :] = acc

            @pl.when(t > 0)
            def _():
                out_ref[f, :, :] = out_ref[f, :, :] + acc

    return _gather_grid_call(
        rows, idx, pos3, g3, h3, ids2, jnp.int32, kernel, F, B, bm_g,
        interpret,
    )


def hist_wave_gather(
    rows,
    idx,
    pos_g,
    g,
    h,
    node_ids,
    F: int,
    B: int,
    *,
    precision: str,
    kernels: str,
    bm_g: int = BMG_DEFAULT,
    interpret: bool = False,
):
    """(N, F, B, 3) partial histograms over a compacted row subset of
    `rows`, the gather_table() of the wave's (F, n) bin matrix.

    kernels="pallas" runs the fused gather+hist kernel (`interpret` forces
    it through the Pallas interpreter whatever the family, for tests);
    "dense" runs the same math as an explicit (R, F) gather + dense einsum
    — bit-identical at precision="int8". Output dtype as hist_wave's.
    """
    N = node_ids.shape[0]
    with scope("gbdt.hist"):
        if kernels == "pallas" or interpret:
            if precision == "int8":
                out = _hist_gather_pallas_q(
                    rows, idx, pos_g, g, h, node_ids, F, B, bm_g, interpret
                )
            else:
                out = _hist_gather_pallas(
                    rows, idx, pos_g, g, h, node_ids, F, B, bm_g,
                    precision == "bf16", interpret,
                )
        else:
            bt = jnp.transpose(jnp.take(rows, idx, axis=0)[:, :F])
            out = _hist_dense_at(precision, bt, pos_g, g, h, node_ids, B)
        out = out.reshape(F, 3, N, B)
        return jnp.transpose(out, (2, 0, 3, 1))


def compact_indices(mask, R: int):
    """Order-preserving compaction of a boolean row mask into a static
    (R,) index buffer: `idx[:cnt]` are the positions of the True entries
    in ascending order, slots at/past `cnt` point at row 0 (callers mask
    them out — the fused gather kernel via pos_g = -1, the GOSS fit set
    via an `arange(R) < cnt` validity mask). Shared by the engine's
    leaf-partitioned budget gathers and the per-tree GOSS row selection,
    so both hot paths compact rows with the same scatter idiom.

    Returns (idx (R,) int32, cnt () int32). Requires R >= true-count
    (overflow entries are dropped by the scatter's drop mode — callers
    size R from static knowledge)."""
    n = mask.shape[0]
    csum = jnp.cumsum(mask.astype(jnp.int32))
    cnt = csum[-1]
    dest = jnp.where(mask, csum - 1, R)
    idx = jnp.zeros((R,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    return idx, cnt


def pad_inputs(
    bins: np.ndarray, bm: int = BM_DEFAULT, n_pad: int = None, F_pad: int = None
):
    """Host-side one-time prep: transpose + pad the bin matrix for hist_wave.

    Returns (bins_t (F_pad, n_pad) int32, n_pad). Padding rows get bin 0
    but are excluded by pos = -1; padded FEATURES (mesh feature-slice
    alignment) are all-bin-0 and masked by the caller. Pass `n_pad` to pad
    to an explicit target (multi-process shard equalization) instead of
    the next bm multiple."""
    n, F = bins.shape
    if n_pad is None:
        n_pad = _pad_to(n, bm)
    if F_pad is None:
        F_pad = F
    bins_t = np.zeros((F_pad, n_pad), np.int32)
    bins_t[:F, :n] = bins.T
    return bins_t, n_pad
