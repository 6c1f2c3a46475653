"""Sample-position routing kernel — the SamplePositionData equivalent.

The XLA formulation (_route_dense) runs NW sequential full-array
passes per wave: each slot re-reads one bins row (42 MB at 10.5M rows)
AND rewrites the whole pos array — ~1.3 GB of HBM traffic per 16-slot
wave. The Pallas kernel does the whole wave in ONE pass: per sample block
it loads the block's bin rows once, resolves every slot's compare/select
in VMEM, and writes pos once (~0.3 GB per wave with uint8 bins).

Reference: SamplePositionData.resetPosition:115 (partition samples of a
split node between its children).

`leaf_values` is the read at the end of that walk: each row's final node
id looked up in the tree's leaf table. XLA lowers `leaf[pos]` to its
general gather at 8.2 ns an index whatever the table's size (86 ms a tree
at 10.5M rows for one of 509 floats: ledger PR 33); the Pallas kernel
resolves a block's rows in registers in one pass over `pos` (0.26 ms a
tree in the round program: my chip runs, PR 34, PERF.md section 5).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .hist import tile_bins


# The routing kernel is handed every feature's bins of a row block, F x bm
# cells of 4 bytes in VMEM (a one-byte tile with a size-1 sublane dim takes
# a word a bin), double-buffered: 3.7 MB at 28 features and bm 16,384; on
# the v5e (16 MiB of scoped VMEM) Mosaic refuses it from 128 features on.
# Past ROUTE_VMEM_BYTES a wave is routed by bins row on the untiled matrix
# (`_route_dense`: 64 row reads of n bytes, whatever F), GrowSpec.route.
ROUTE_VMEM_BYTES = 12 << 20


def route_kernel_holds(F: int, bm: int) -> bool:
    """Whether `gbdt_route`'s block holds F features."""
    return 2 * F * bm * 4 <= ROUTE_VMEM_BYTES


@partial(jax.jit, static_argnames=("bm", "interpret"))
def _route_pallas(
    bins4, pos, valid, nid, feat, slot, lo, hi, lch, rch, bm: int,
    interpret: bool = False,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, nblk = bins4.shape[0], bins4.shape[1]
    n = nblk * bm
    NW = nid.shape[0]
    pos3 = pos.reshape(nblk, 1, bm)
    # pack the per-slot scalars into one (8, NW) i32 table (SMEM-resident);
    # rows 6/7 carry the split's EFB member range [lo, hi] — a row goes
    # right only when its bin is inside the range AND above the slot
    # (plain columns pass lo=0/hi=B-1, reducing to the bin > slot compare)
    tab = jnp.stack(
        [
            valid.astype(jnp.int32),
            nid,
            feat,
            slot,
            lch,
            rch,
            lo,
            hi,
        ]
    )

    def kernel(tab_ref, bins_ref, pos_ref, out_ref):
        p = pos_ref[0, 0, :][None, :]  # (1, bm)
        newp = p
        for i in range(NW):
            f = tab_ref[2, i]
            row = bins_ref[pl.ds(f, 1), 0, 0, :]  # (1, bm), dynamic sublane
            ri = row.astype(jnp.int32)
            m = (p == tab_ref[1, i]) & (tab_ref[0, i] != 0)
            go_right = (
                (ri > tab_ref[3, i]) & (ri >= tab_ref[6, i]) & (ri <= tab_ref[7, i])
            )
            child = jnp.where(go_right, tab_ref[5, i], tab_ref[4, i])
            newp = jnp.where(m, child, newp)
        out_ref[0, 0, :] = newp[0]

    return pl.pallas_call(
        kernel,
        name="gbdt_route",
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((F, 1, 1, bm), lambda k: (0, k, 0, 0)),
            pl.BlockSpec((1, 1, bm), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk, 1, bm), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(tab, bins4, pos3).reshape(n)


def _route_dense(
    bins_t, pos, sel_valid, sel_nid, sel_feat, sel_slot, sel_lo, sel_hi,
    sel_l, sel_r,
):
    """Move samples of each wave node to its children: one bins_t row
    dynamic-slice + compare per wave slot (masked no-op when invalid).

    sel_lo/sel_hi bound the split's EFB member range: a row goes right
    only when its bin is inside [lo, hi] AND above the slot — bins
    outside the range are other bundle members (the split feature's
    default/zero value, which sits left). Plain columns pass lo=0,
    hi=B-1, reducing to the original `bin > slot` compare."""
    n = pos.shape[0]

    def body(i, pos):
        f = jnp.maximum(sel_feat[i], 0)
        row = jax.lax.dynamic_slice(bins_t, (f, jnp.zeros((), f.dtype)), (1, n))[0]
        row = row.astype(jnp.int32)
        go_right = (row > sel_slot[i]) & (row >= sel_lo[i]) & (row <= sel_hi[i])
        child = jnp.where(go_right, sel_r[i], sel_l[i])
        upd = jnp.where(pos == sel_nid[i], child, pos)
        return jnp.where(sel_valid[i], upd, pos)

    return jax.lax.fori_loop(0, sel_nid.shape[0], body, pos)


@partial(jax.jit, static_argnames=("bm", "interpret"))
def _leaf_values_pallas(leaf, pos, bm: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, M = pos.shape[0], leaf.shape[0]
    rows = bm // 128
    assert bm % 1024 == 0 and n % bm == 0, (n, bm)
    # the table as (nseg, 128): node id = 128 * row + lane. Both reshapes
    # of the row axis are bitcasts on the chip (a block is the bm rows
    # gbdt_route's is, as `rows` full registers instead of one sublane)
    nseg = -(-M // 128)
    tab = jnp.pad(leaf, (0, nseg * 128 - M)).reshape(nseg, 128)
    pos2 = pos.reshape(n // 128, 128)

    def kernel(tab_ref, pos_ref, out_ref):
        p = pos_ref[...]  # (rows, 128)
        lane, seg = p & 127, p >> 7
        acc = jnp.zeros(p.shape, jnp.float32)
        for j in range(nseg):
            # in-register lane gather from table row j, kept where the
            # node id lies in that row: moves bits, rounds nothing
            tj = jnp.broadcast_to(tab_ref[j : j + 1, :], p.shape)
            gj = jnp.take_along_axis(
                tj, lane, axis=1, mode="promise_in_bounds"
            )
            acc = jnp.where(seg == j, gj, acc)
        out_ref[...] = acc

    return pl.pallas_call(
        kernel,
        name="gbdt_leaf_values",
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((nseg, 128), lambda k: (0, 0)),
            pl.BlockSpec((rows, 128), lambda k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((rows, 128), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(tab, pos2).reshape(n)


def leaf_values(
    leaf, pos, *, kernels: str, bm: int, mesh=None, axis: str = "data",
    interpret: bool = False,
):
    """Each row's leaf value, f32[n], bit for bit `leaf[pos]` for node ids
    in [0, len(leaf)): the one-pass kernel (kernels="pallas"; cost grows
    with len(leaf) / 128, so the caller asks GrowSpec.leaf_lookup which
    family a tree's size takes) or XLA's gather ("dense").

    leaf: (M,) f32, replicated; pos: (n,) i32, n a multiple of bm per
    shard. mesh (of > 1 devices): the rows are sharded over `axis`, so the
    kernel runs a shard under shard_map and no device fetches for rows it
    does not hold. `interpret` runs the kernel through the Pallas
    interpreter (CPU tests)."""
    if kernels != "pallas":
        return leaf[pos]
    fn = partial(_leaf_values_pallas, bm=bm, interpret=interpret)
    if mesh is None or mesh.devices.size == 1:
        return fn(leaf, pos)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
        check_vma=False,
    )(leaf, pos)


def route_wave(
    bins_t, pos, valid, nid, feat, slot, lch, rch, lo, hi, *, kernels: str,
    bm: int, interpret: bool = False,
):
    """One wave's routing: the one-pass kernel (kernels="pallas") or its
    XLA twin ("dense"), as the caller says (GrowSpec.route).

    bins_t: (F, n) or pre-tiled (F, nblk, 1, bm). lo/hi: per-slot EFB
    member-range bounds (see _route_dense)."""
    if kernels == "pallas":
        bins4 = bins_t if bins_t.ndim == 4 else tile_bins(bins_t, bm)
        return _route_pallas(
            bins4, pos, valid, nid,
            jnp.maximum(feat, 0), slot, lo, hi, lch, rch, bm, interpret,
        )
    bins2 = bins_t if bins_t.ndim == 2 else bins_t.reshape(bins_t.shape[0], -1)
    return _route_dense(bins2, pos, valid, nid, feat, slot, lo, hi, lch, rch)
