"""Fused compact+gather+histogram kernel (gbdt/hist.hist_wave_gather).

The fused kernel is the r6 TPU default for leaf-partitioned budget waves;
off-TPU it cannot compile, so these tests drive the REAL kernel body
through the Pallas interpreter (`interpret=True`) and pin it against the
dense einsum path — exactly (int8: order-independent i32 sums) and to
float tolerance (f32). The engine-level tests grow whole trees with the
fused budget rungs enabled and require them identical to full-scan
growth, single-device and under the 8-device shard_map mesh.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytklearn_tpu.gbdt.engine import GrowSpec, make_grow_tree
from ytklearn_tpu.gbdt.hist import (
    gather_table,
    hist_wave,
    hist_wave_gather,
)


def _case(n=4096, F=6, B=16, seed=0):
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    pos = rng.randint(-1, 6, size=(n,)).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    ids = np.asarray([0, 2, 4, -2], np.int32)
    return rows, pos, g, h, ids


def _compact(pos, g, h, ids, R):
    """Host mirror of the engine's compaction (mask -> cumsum -> scatter)."""
    mask = np.isin(pos, ids[ids >= 0])
    sel = np.nonzero(mask)[0]
    assert len(sel) <= R, "test budget must hold the wave"
    idx = np.zeros(R, np.int32)
    idx[: len(sel)] = sel
    pg = np.full(R, -1, np.int32)
    pg[: len(sel)] = pos[sel]
    gg = np.zeros(R, np.float32)
    gg[: len(sel)] = g[sel]
    hg = np.zeros(R, np.float32)
    hg[: len(sel)] = h[sel]
    return idx, pg, gg, hg


def test_fused_kernel_matches_dense_f32():
    rows, pos, g, h, ids = _case()
    B, R, bm_g = 16, 3072, 256
    idx, pg, gg, hg = _compact(pos, g, h, ids, R)
    ref = np.asarray(
        hist_wave(
            jnp.asarray(rows.T.astype(np.int32)), jnp.asarray(pos),
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(ids), B,
            precision="f32", kernels="dense",
        )
    )
    got = np.asarray(
        hist_wave_gather(
            gather_table(jnp.asarray(rows.T)), jnp.asarray(idx),
            jnp.asarray(pg), jnp.asarray(gg), jnp.asarray(hg),
            jnp.asarray(ids), rows.shape[1], B,
            precision="f32", kernels="dense", bm_g=bm_g, interpret=True,
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_fused_kernel_matches_dense_int8_exact():
    rows, pos, g, h, ids = _case(seed=3)
    B, R, bm_g = 16, 3072, 512
    gi = np.round(np.clip(g * 20, -127, 127)).astype(np.float32)
    hi = np.round(np.clip(h * 20, 0, 127)).astype(np.float32)
    idx, pg, gg, hg = _compact(pos, gi, hi, ids, R)
    ref = np.asarray(
        hist_wave(
            jnp.asarray(rows.T.astype(np.int32)), jnp.asarray(pos),
            jnp.asarray(gi), jnp.asarray(hi), jnp.asarray(ids), B,
            precision="int8", kernels="dense",
        )
    )
    got = np.asarray(
        hist_wave_gather(
            gather_table(jnp.asarray(rows.T)), jnp.asarray(idx),
            jnp.asarray(pg), jnp.asarray(gg), jnp.asarray(hg),
            jnp.asarray(ids), rows.shape[1], B,
            precision="int8", kernels="dense", bm_g=bm_g, interpret=True,
        )
    )
    np.testing.assert_array_equal(got, ref)
    # the dense family (what runs off-TPU in production) lands on the
    # identical i32 sums
    got_dense = np.asarray(
        hist_wave_gather(
            gather_table(jnp.asarray(rows.T)), jnp.asarray(idx),
            jnp.asarray(pg), jnp.asarray(gg), jnp.asarray(hg),
            jnp.asarray(ids), rows.shape[1], B,
            precision="int8", kernels="dense", bm_g=bm_g,
        )
    )
    np.testing.assert_array_equal(got_dense, ref)


def test_fused_kernel_wide_bins():
    """B > 256: bin ids past the uint8 range survive the int32 table."""
    rng = np.random.RandomState(7)
    n, F, B = 2048, 3, 512
    rows = rng.randint(0, B, size=(n, F)).astype(np.int32)
    pos = rng.randint(0, 2, size=(n,)).astype(np.int32)
    g = np.round(rng.randn(n) * 5).astype(np.float32)
    h = np.abs(np.round(rng.randn(n) * 5)).astype(np.float32)
    ids = np.asarray([0, 1], np.int32)
    idx, pg, gg, hg = _compact(pos, g, h, ids, n)
    ref = np.asarray(
        hist_wave(
            jnp.asarray(rows.T), jnp.asarray(pos), jnp.asarray(g),
            jnp.asarray(h), jnp.asarray(ids), B,
            precision="int8", kernels="dense",
        )
    )
    got = np.asarray(
        hist_wave_gather(
            gather_table(jnp.asarray(rows.T)), jnp.asarray(idx),
            jnp.asarray(pg), jnp.asarray(gg), jnp.asarray(hg),
            jnp.asarray(ids), rows.shape[1], B,
            precision="int8", kernels="dense", bm_g=256, interpret=True,
        )
    )
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Whole-engine equivalence with the fused budget rungs enabled
# ---------------------------------------------------------------------------


def _grow_case(n=6144, F=6, B=32, seed=11):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    logit = 0.1 * bins[:, 0] - 0.07 * bins[:, 1] + 0.4 * (bins[:, 2] > 16)
    y = (logit + rng.randn(n) > 0.5).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(logit - 0.5))).astype(np.float32)
    g = (p - y).astype(np.float32)
    h = np.maximum(p * (1 - p), 1e-6).astype(np.float32)
    return bins, g, h


def _spec(F, B, **over):
    kw = dict(
        F=F, B=B, max_nodes=31, wave=4, policy="loss", max_depth=20,
        max_leaves=16, lr=0.1, l1=0.0, l2=1.0, min_h=1.0, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0, precision="int8",
        kernels="dense", ladder=(4, 16), fused_max_rows=1 << 18, bm_g=512,
    )
    kw.update(over)
    return GrowSpec(**kw)


def _grow_tree_sig(spec, bins, g, h, mesh=None):
    grow = make_grow_tree(spec, mesh=mesh)
    n, F = bins.shape
    args = (
        jnp.asarray(np.ascontiguousarray(bins.T)),
        jnp.ones((n,), bool),
        jnp.asarray(g),
        jnp.asarray(h),
        jnp.ones((F,), bool),
    )
    if mesh is not None and mesh.devices.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        shardings = (
            NamedSharding(mesh, P(None, "data")),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P("data")),
        )
        args = tuple(jax.device_put(a, s) for a, s in zip(args, shardings))
    tr, pos, _aux, wlog = jax.jit(lambda *a: grow(*a))(*args)
    sig = {
        "feat": np.asarray(tr.feat).tolist(),
        "slot": np.asarray(tr.slot).tolist(),
        "left": np.asarray(tr.left).tolist(),
        "right": np.asarray(tr.right).tolist(),
        "leaf": np.round(np.asarray(tr.leaf), 6).tolist(),
        "n_nodes": int(tr.n_nodes),
    }
    return sig, np.asarray(wlog)


def test_fused_engine_matches_full_scan_exact():
    """Trees grown with the fused budget rungs (Pallas interpreter) must be
    IDENTICAL to full-scan growth: same rows enter every histogram and
    int8 i32 sums are order-independent."""
    bins, g, h = _grow_case()
    sig_fused, wlog = _grow_tree_sig(_spec(6, 32, fused_interpret=True), bins, g, h)
    sig_full, _ = _grow_tree_sig(_spec(6, 32, ladder=()), bins, g, h)
    assert sig_fused == sig_full
    # the wave log proves late waves ran at partitioned budgets: at least
    # one histogram pass scanned fewer rows than the full 6144
    used = wlog[wlog[:, 3] > 0]
    assert used[0, 0] == bins.shape[0]  # root pass scans everything
    assert used[:, 0].min() < bins.shape[0]  # some wave ran partitioned
    # and every budget pass was big enough for its wave's need
    assert (used[:, 0] >= used[:, 1]).all()


def test_fused_engine_sharded_matches_single(mesh8):
    """Fused budget rungs under shard_map (per-shard compaction + interpret
    kernel + psum_scatter) must grow the identical int8 tree to one
    device."""
    bins, g, h = _grow_case(n=8192, seed=5)
    # F=6 doesn't divide 8 devices; pad features like the trainer does
    Fp = 8
    bins_p = np.zeros((bins.shape[0], Fp), np.int32)
    bins_p[:, : bins.shape[1]] = bins
    spec1 = _spec(Fp, 32, fused_interpret=True, bm_g=256, ladder=(8,))
    sig1, _ = _grow_tree_sig(spec1, bins_p, g, h)
    sig8, _ = _grow_tree_sig(spec1, bins_p, g, h, mesh=mesh8)
    assert sig1 == sig8


def test_fused_rung_selection():
    """Ladder rungs above fused_max_rows must fall back to the XLA gather
    implementation, below it to the fused kernel — both exact in int8."""
    bins, g, h = _grow_case(n=4096, seed=9)
    sig_mixed, _ = _grow_tree_sig(
        _spec(6, 32, fused_interpret=True, fused_max_rows=512, ladder=(4, 16),
              bm_g=256),
        bins, g, h,
    )
    sig_full, _ = _grow_tree_sig(_spec(6, 32, ladder=()), bins, g, h)
    assert sig_mixed == sig_full
