"""What 2,000 columns forced on the GBDT path (gbdt_epsilon), held on the CPU.

Binning a range of columns at a time gives the whole matrix's quantiles and
bin ids bit for bit; the packed tiles hold the bins the kernel takes apart
in row order; the full-scan kernel through the Pallas interpreter equals its
dense twin at a column count no group width divides and on packed words;
what the width decides (route family, rungs, packing) is one table of
`_grow_spec`'s; at Higgs' shape the traced round program is the one it was
before any of this; and a wide run agrees with the plain reference
(perfbench/reference/gbdt_ref.py) in the dense family and, through the
interpreter, with every wide branch taken.
"""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu.config.params import ApproximateSpec
from ytklearn_tpu.gbdt import binning, hist
from ytklearn_tpu.gbdt import trainer as trainer_mod
from ytklearn_tpu.gbdt.trainer import GBDTTrainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (1) binning a range of columns at a time -------------------------------


def _columns(n=3000, F=11, seed=3):
    """Seeded columns with what a chunk border could get wrong: ties, a
    constant column, a column of few distinct values, a heavy tail."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    X[:, 1] = np.round(X[:, 1], 1)  # ties
    X[:, 4] = 2.5  # constant
    X[:, 6] = rng.randint(0, 5, n)  # five values
    X[:, 9] = np.exp(3 * X[:, 9])
    return X


@pytest.fixture
def small_budget(monkeypatch):
    """Binning's byte budget cut so that 11 columns of 3,000 rows go in
    four parts (3, 3, 3, 2), as 2,000 columns of 400,000 go in twelve."""
    monkeypatch.setattr(binning, "WHOLE_BYTES", 11 * 3000 * 4 - 1)
    monkeypatch.setattr(binning, "CHUNK_BYTES", 3 * 3000 * 4)


def test_feature_chunk_table():
    assert binning.feature_chunk(28, 10_500_000) == 28  # 1.18 GB: whole
    assert binning.feature_chunk(2000, 400_000) == 167  # 12 parts
    assert binning.feature_chunk(2000, 409_600) == 154  # tiles: 13 parts
    assert binning.feature_chunk(137, 2_270_000) == 137  # 1.24 GB: whole


@pytest.mark.parametrize("weighted", [False, True], ids=["uniform", "weighted"])
def test_chunked_quantiles_and_bin_ids_equal_whole(small_budget, weighted):
    X = _columns()
    n, F = X.shape
    w = (np.random.RandomState(1).rand(n).astype(np.float32) + 0.5
         if weighted else np.ones(n, np.float32))
    spec = ApproximateSpec(max_cnt=31, use_sample_weight=weighted, alpha=1.0)
    cols = binning.ColumnsT(X)
    assert cols.whole is None and cols.n_chunks == 4
    assert [(lo, hi) for lo, hi, _ in cols.chunks()] == [
        (0, 3), (3, 6), (6, 9), (9, 11)]
    whole = binning.quantile_bins_device(jnp.asarray(X.T), w, spec)
    parts = binning.quantile_bins_device(cols, w, spec)
    for a, b in zip(whole, parts):
        np.testing.assert_array_equal(a, b)

    from ytklearn_tpu.config.params import GBDTParams

    p = GBDTParams(approximate=[spec])
    bins_whole = binning.build_bins_maybe_device(X, jnp.asarray(X.T), w, p)
    bins_parts = binning.build_bins_maybe_device(X, cols, w, p)
    np.testing.assert_array_equal(bins_whole.values, bins_parts.values)
    np.testing.assert_array_equal(bins_whole.counts, bins_parts.counts)
    n_pad = 3072
    ids_whole = binning.bin_matrix_device(
        jnp.pad(jnp.asarray(X.T), ((0, 0), (0, n_pad - n))), bins_whole)
    ids_parts = binning.bin_matrix_device(
        cols, bins_parts, n_pad=n_pad, dtype=jnp.uint8)
    assert ids_parts.dtype == jnp.uint8 and ids_parts.shape == (F, n_pad)
    np.testing.assert_array_equal(np.asarray(ids_whole), np.asarray(ids_parts))
    # and the host's rule
    np.testing.assert_array_equal(
        np.asarray(ids_parts)[:, :n].T, binning.bin_matrix(X, bins_whole))


def test_columns_whole_under_the_budget():
    cols = binning.ColumnsT(_columns())
    assert cols.whole is not None and cols.n_chunks == 1
    (lo, hi, part), = cols.chunks()
    assert (lo, hi) == (0, 11) and part is cols.whole


def test_chunked_column_stats_plan_like_whole(small_budget):
    """EFB's column statistics a range of columns at a time: the plan of a
    sparse one-of-K block equals the whole matrix's."""
    rng = np.random.RandomState(0)
    n = 3000
    X = rng.randn(n, 11).astype(np.float32)
    hot = rng.randint(0, 6, n)
    for j in range(6):  # columns 2..7: mutually exclusive, non-negative
        X[:, 2 + j] = np.where(hot == j, 1.0 + rng.rand(n), 0.0)
    from ytklearn_tpu.config.params import GBDTParams

    p = GBDTParams(approximate=[ApproximateSpec(max_cnt=15)])
    bins = binning.build_bins(X, np.ones(n, np.float32), p)
    whole = binning.build_bundle_plan(jnp.asarray(X.T), bins, 0, 64)
    parts = binning.build_bundle_plan(binning.ColumnsT(X), bins, 0, 64)
    assert whole is not None and whole.bundles == parts.bundles
    assert whole.member_lo == parts.member_lo
    np.testing.assert_array_equal(whole.col_fid, parts.col_fid)


# -- (2) the packed tiles ---------------------------------------------------


def _unpack(words, bm):
    """The bins a kernel block reads from tile_bins' words, in row order."""
    w = np.asarray(words)
    F, nblk = w.shape[:2]
    q = w.reshape(F, nblk, bm // 4)
    rows = np.concatenate([(q >> s) & 255 for s in (0, 8, 16, 24)], axis=2)
    return rows.reshape(F, nblk * bm)


@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
def test_packed_tiles_hold_the_bins_in_row_order(monkeypatch, chunked):
    bm, F, n = 512, 7, 2048
    bins_t = np.random.RandomState(2).randint(0, 256, (F, n)).astype(np.uint8)
    if chunked:  # three features a part: 3, 3 and the last part from 4
        monkeypatch.setattr(binning, "WHOLE_BYTES", F * n * 4 - 1)
        monkeypatch.setattr(binning, "CHUNK_BYTES", 3 * n * 4)
        assert binning.feature_chunk(F, n) == 3
    words = jax.jit(lambda b: hist.tile_bins(b, bm, pack=True))(jnp.asarray(bins_t))
    assert words.shape == (F, n // bm, 1, bm // 4) and words.dtype == jnp.int32
    np.testing.assert_array_equal(_unpack(words, bm), bins_t)
    # the unpacked layout is the parent's
    tiles = hist.tile_bins(jnp.asarray(bins_t), bm)
    assert tiles.shape == (F, n // bm, 1, bm) and tiles.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(tiles).reshape(F, n), bins_t)


# -- (3) the full-scan kernel through the interpreter -----------------------


@pytest.mark.parametrize("precision", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("F,packed", [(137, False), (137, True), (16, True)],
                         ids=["F137", "F137-packed", "F16-packed"])
def test_scan_kernel_interpreted_equals_dense_twin(F, packed, precision):
    """F = 137: no group width divides it (_pick_fg -> 1); 16: groups of 8,
    as 2,000 columns have. Packed words or one-byte tiles, the kernel's
    histograms are the dense twin's (int8: bit for bit)."""
    assert hist._pick_fg(137) == 1 and hist._pick_fg(16) == 8
    assert hist._pick_fg(2000) == 8 and hist._pick_fg(28) == 14
    bm, nblk, B, N = 512, 2, 32, 5
    n = bm * nblk
    rng = np.random.RandomState(F)
    bins_t = rng.randint(0, B, (F, n)).astype(np.uint8)
    pos = rng.randint(-1, 9, n).astype(np.int32)
    if precision == "int8":
        g = rng.randint(-127, 128, n).astype(np.float32)
        h = rng.randint(0, 128, n).astype(np.float32)
    else:
        g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    ids = np.array([3, 0, 7, -2, 5], np.int32)
    tiles = hist.tile_bins(jnp.asarray(bins_t), bm, pack=packed)
    args = (jnp.asarray(pos), jnp.asarray(g), jnp.asarray(h), jnp.asarray(ids), B)
    got = hist.hist_wave(tiles, *args, precision=precision, kernels="pallas",
                         bm=bm, interpret=True)
    want = hist.hist_wave(jnp.asarray(bins_t), *args, precision=precision,
                          kernels="dense", bm=bm)
    assert got.shape == (N, F, B, 3) and got.dtype == want.dtype
    if precision == "int8":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-4)
    # counts are exact at every precision
    np.testing.assert_array_equal(np.asarray(got[..., 2]), np.asarray(want[..., 2]))


@pytest.mark.parametrize("precision", ["bf16", "f32"])
@pytest.mark.parametrize("packed", [False, True], ids=["one-byte", "packed"])
@pytest.mark.parametrize("B", [256, 24])
@pytest.mark.parametrize("N", [1, 2, 4, 8, 16, 32, 64])
def test_factored_scan_interpreted_equals_dense_twin(N, B, packed, precision):
    """At every wave width of a tree, on one-byte tiles and packed words:
    where the kernel factors the bin one-hot (B = 256, N < 32) and where it
    builds the whole one-hot (N >= 32; B = 24, no power of two), its counts
    are the dense twin's bit for bit and its sums within the unfactored
    kernel's tolerance."""
    H = hist.onehot_split(N, B)
    assert (H > 1) == (B == 256 and N < 32)
    F, bm, nblk = 16, 512, 2
    n = bm * nblk
    rng = np.random.RandomState(N * 1000 + B)
    bins_t = rng.randint(0, B, (F, n)).astype(np.uint8)
    pos = rng.randint(-1, N + 3, n).astype(np.int32)
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    ids = rng.permutation(N + 2)[:N].astype(np.int32)
    ids[N // 2] = -2  # a padded slot matches no row
    tiles = hist.tile_bins(jnp.asarray(bins_t), bm, pack=packed)
    args = (jnp.asarray(pos), jnp.asarray(g), jnp.asarray(h), jnp.asarray(ids), B)
    got = hist.hist_wave(tiles, *args, precision=precision, kernels="pallas",
                         bm=bm, interpret=True)
    want = hist.hist_wave(jnp.asarray(bins_t), *args, precision=precision,
                          kernels="dense", bm=bm)
    assert got.shape == (N, F, B, 3) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got[..., 2]), np.asarray(want[..., 2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


def test_onehot_split_table():
    """H from (N, B) alone: the least of max(compares, 6N*H), the compares
    B for the whole one-hot and H + B/H factored."""
    assert [hist.onehot_split(N, 256) for N in (1, 2, 4, 8, 16, 32, 64, 128)] == [
        8, 4, 4, 2, 2, 1, 1, 1]
    assert hist.onehot_split(1, 24) == 1 and hist.onehot_split(1, 255) == 1
    assert hist.onehot_split(1, 8) == 1  # max(2 + 4, 12) is not under 8
    assert hist.onehot_split(1, 32) == 2
    assert [hist.onehot_split(N, 512) for N in (1, 16, 32, 64)] == [8, 2, 2, 1]


# -- (4) what the width decides: one table ----------------------------------

_SHAPES = {  # rows padded to bm 16384: Higgs, MS LTR, Epsilon
    "higgs": (10_502_144, 28), "msltr": (2_277_376, 137), "epsilon": (409_600, 2000),
}
_CHOICES = {
    ("higgs", "tpu"): ("pallas", False, ((41_984, "fused"), (164_864, "fused"))),
    ("msltr", "tpu"): ("dense", True, ((147_456, "xla"), (573_440, "xla"))),
    ("epsilon", "tpu"): ("dense", True, ((32_768, "xla"), (114_688, "xla"))),
    ("higgs", "cpu"): ("dense", False, ((328_192, "xla"), (1_312_768, "xla"))),
    ("msltr", "cpu"): ("dense", False, ((71_168, "xla"), (284_672, "xla"))),
    ("epsilon", "cpu"): ("dense", False, ((12_800, "xla"), (51_200, "xla"))),
}


def _spec_on(monkeypatch, backend, F, tmp_path, **kw):
    from test_gbdt_engine import _params

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    p = _params(tmp_path, "loss", max_leaf_cnt=255, min_child_hessian_sum=100.0)
    return GBDTTrainer(p, engine="device", **kw)._grow_spec(F, 256)


@pytest.mark.parametrize("shape,backend", sorted(_CHOICES))
def test_grow_spec_choices_by_width(tmp_path, monkeypatch, shape, backend):
    n, F = _SHAPES[shape]
    spec = _spec_on(monkeypatch, backend, F, tmp_path)
    route, packed, rungs = _CHOICES[shape, backend]
    assert (spec.route, spec.packed, spec.rungs(n)) == (route, packed, rungs)
    assert spec.kernels == ("pallas" if backend == "tpu" else "dense")
    wide = backend == "tpu" and shape != "higgs"
    assert spec.ladder == (trainer_mod.WIDE_LADDER if wide
                           else trainer_mod.LADDER[spec.kernels])
    assert spec.fused_max_rows == (0 if wide else trainer_mod.FUSED_MAX_ROWS)


def test_width_predicates_borders():
    from ytklearn_tpu.gbdt import route

    assert route.route_kernel_holds(96, 16384)
    assert not route.route_kernel_holds(97, 16384)
    assert hist.fused_holds(28, 64, 256) and hist.fused_holds(61, 64, 256)
    assert not hist.fused_holds(62, 64, 256)
    # int32 bins (more than 256) are never packed
    from test_gbdt_engine import _rung_spec

    assert not _rung_spec(F=2000, B=512, kernels="pallas").packed
    assert _rung_spec(F=2000, B=256, kernels="pallas").packed


# -- (5) Higgs' round program is the one it was taken from -----------------

# sha256 of the traced round program (its jaxpr's text, addresses struck out)
# at gbdt_higgs.train's true shape, 10,502,144 + 507,904 padded rows x 28, in
# the Pallas family. First taken at e3fac9c; taken again on purpose when the
# narrow waves' kernel came to factor the bin one-hot (on top of 9b12f90: the
# six scans under 32 nodes and the `gbdt.hist.start` subscope changed; the
# 32- and 64-node scans' kernel is the one before, jaxpr for jaxpr). A change
# to the GBDT round program at Higgs' width changes it: take it again and say
# in PERF.md what moved.
HIGGS_ROUND_SHA = "21c03c29bbe8a3e6f38086586cb76f522377af7365b03c22775ca588c2b6c756"


def _round_program_text(monkeypatch, tmp_path, n_rows, nt_rows, F):
    from test_gbdt_engine import _params

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = _params(
        tmp_path, "loss", round_num=100, max_leaf_cnt=255, max_depth=-1,
        learning_rate=0.1, min_child_hessian_sum=100.0,
        approximate=[ApproximateSpec(max_cnt=255)],
    )
    tr = GBDTTrainer(p, mesh=None)
    spec = tr._grow_spec(F, 256)
    bm = spec.bm
    n, nt = -(-n_rows // bm) * bm, -(-nt_rows // bm) * bm
    dd = trainer_mod._DevInputs(
        bins=None, bins_t=None, y=None, weight=None, real_mask=None,
        n_score=n, F=F, F_prog=F, B=256, D=1, aux_bins=(), y_t=None,
        w_t=None, nt_score=nt,
    )
    tr._efb_plan = None
    jit_round = tr._build_round_step(dd, spec, True)
    S = jax.ShapeDtypeStruct
    bufs, lb, tlb = tr._make_tree_bufs(spec.max_nodes)
    carry = (S((n,), jnp.float32), S((nt,), jnp.float32), bufs, lb, tlb)
    data = (S((F, n), jnp.uint8), S((n,), jnp.float32), S((n,), jnp.float32),
            S((n,), jnp.bool_), S((F, nt), jnp.uint8), S((nt,), jnp.float32),
            S((nt,), jnp.float32))
    with jax.enable_x64(False):  # as the program runs
        jx = jax.make_jaxpr(jit_round)(
            carry, jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0), data)
    return spec, re.sub(r"0x[0-9a-f]+", "0x", str(jx))


def test_higgs_round_program_is_the_parents(tmp_path, monkeypatch):
    spec, text = _round_program_text(monkeypatch, tmp_path, 10_500_000, 500_000, 28)
    assert (spec.kernels, spec.route, spec.packed) == ("pallas", "pallas", False)
    assert hashlib.sha256(text.encode()).hexdigest() == HIGGS_ROUND_SHA


def test_wide_round_program_traces_with_every_wide_branch(tmp_path, monkeypatch):
    spec, text = _round_program_text(monkeypatch, tmp_path, 400_000, 100_000, 2000)
    assert (spec.route, spec.packed) == ("dense", True)
    assert "gbdt_hist_scan" in text and "gbdt_route" not in text
    assert "gbdt_hist_gather" not in text and "gbdt_leaf_values" in text
    # the packed words of the full scans and of both gathered budgets
    for shape in ("i32[2000,25,1,4096]", "i32[2000,7,1,4096]", "i32[2000,2,1,4096]"):
        assert shape in text, shape
    # and no one-byte tile, of train or test rows
    assert "u8[2000,25,1,16384]" not in text and "u8[2000,7,1,16384]" not in text
    # the narrow waves' kernel writes the factored form (F, 3N*H, B/H): N =
    # 1, 2, 4, 8, 16 at H = 8, 4, 4, 2, 2; the 32- and 64-node scans the
    # whole one-hot's (F, 3N, B)
    for shape in ("f32[2000,24,32]", "f32[2000,24,64]", "f32[2000,48,64]",
                  "f32[2000,48,128]", "f32[2000,96,128]",
                  "f32[8,96,256]", "f32[8,192,256]"):
        assert shape in text, shape


# -- (6) a wide run against the plain reference -----------------------------


def _harness(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench", "selfcheck"))
    for name in ("run", "tiny"):
        sys.modules.pop(name, None)
    import run as harness
    import tiny

    return harness, tiny


_WIDE_RUNS = {
    # family: train rows, columns, leaves
    "dense": (8192, 256, 31),
    # a budget under n needs three blocks of bm 16,384 rows, and a wave past
    # the slow start needs more than 32 leaves: 49,152 x 128 columns (past
    # the routing kernel's 96 and, at 32 nodes a wave, the fused kernel's
    # 124), 64 leaves
    "pallas-interpreted": (49152, 128, 64),
}


@pytest.mark.parametrize("family", sorted(_WIDE_RUNS))
def test_wide_run_agrees_with_the_plain_reference(tmp_path, monkeypatch, family):
    """Three trees on seeded wide rows through the benchmark's own adapter
    and comparison (trees, per-round losses, final scores, node statistics,
    the root's split against the reference's own candidates): in the dense
    family, and in the Pallas family through the interpreter, where every
    wide branch runs: no routing kernel, packed tiles, an XLA row gather
    into the full-scan kernel at n/4, no fused rung."""
    harness, tiny = _harness(monkeypatch)
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path / "work"))
    jax.config.update("jax_enable_compilation_cache", False)
    rows, F, leaves = _WIDE_RUNS[family]
    conf = open(os.path.join(ROOT, "perfbench", "configs", "gbdt_epsilon.conf")).read()
    conf = conf.replace("max_leaf_cnt : 255", f"max_leaf_cnt : {leaves}")
    conf = conf.replace("max_feature_dim : 2000", f"max_feature_dim : {F}")
    path = tmp_path / "wide.conf"
    path.write_text(conf)
    cell = tiny.tiny_cell(
        "gbdt_epsilon.train",
        {"train_rows": rows, "test_rows": 2048, "features": F},
        {"conf": str(path), "round_num": 3, "hist_precision": "f32"},
        {"warm_steps": 1},
    )
    seen = {}
    if family == "pallas-interpreted":
        orig = GBDTTrainer._grow_spec
        real = jax.default_backend

        def grow_spec(self, F, B, goss_scale=1.0):
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            try:
                spec = orig(self, F, B, goss_scale)
            finally:
                monkeypatch.setattr(jax, "default_backend", real)
            seen["spec"] = spec
            return dataclasses.replace(spec, fused_interpret=True)

        monkeypatch.setattr(GBDTTrainer, "_grow_spec", grow_spec)
    got = {}

    def after(run, state):
        got["gauges"] = dict(run.gauges)

    # the job ends by itself: a stop under load could fall before the second tree
    res = harness.drive(cell, 2147483777, 3600.0, False, tiny.CPU_DEVICE, after=after)
    read = {k: v["value"] for k, v in res["compared"].items()}
    assert res["attempted"] == 3 and res["failed"] == 0
    assert res["correct"], read
    assert read["cnt_gap"] == 0 and read["root_thr_off"] == 0, read
    assert read["loss_gap"] < 3e-6 and read["test_loss_gap"] < 3e-6, read
    assert read["score_gap"] < 1e-6, read
    g = got["gauges"]
    M = 2 * leaves - 1
    assert g["gbdt.stat.features"] == F
    assert g["gbdt.stat.hist_pool_bytes"] == M * F * 256 * 3 * 4
    if family == "pallas-interpreted":
        spec = seen["spec"]
        assert (spec.kernels, spec.route, spec.packed) == ("pallas", "dense", True)
        assert spec.rungs(49152) == ((16384, "xla"),)  # n/16 rounds up to it too
        assert g["gbdt.stat.route_kernel"] == 0 and g["gbdt.stat.packed_tiles"] == 1
        assert g["gbdt.stat.rungs_fused"] == 0 and g["gbdt.stat.rungs_xla"] == 1
        assert g["gbdt.stat.hist_part_passes"] > 0
        assert 0 < g["gbdt.stat.hist_part_rows_needed"] <= (
            g["gbdt.stat.hist_part_rows_scanned"])
        # the root's and the five slow-start waves under 32 nodes factor
        # the bin one-hot
        assert g["gbdt.stat.hist_factored_passes"] == 6
    else:
        assert g["gbdt.stat.packed_tiles"] == 0 and g["gbdt.stat.rungs_fused"] == 0
        assert g["gbdt.stat.hist_factored_passes"] == 0
    # the narrow waves' histogram passes, a subscope of the round program
    from ytklearn_tpu.obs import scopes

    assert any("gbdt.hist.start" in ops.values()
               for ops in scopes.subscope_map().values())


# -- (7) the sketch's threads -----------------------------------------------


def test_sketch_threads_give_the_one_thread_payload():
    from ytklearn_tpu.obs import quality

    rng = np.random.RandomState(4)
    X = rng.randn(5000, 9).astype(np.float32)
    X[rng.rand(5000) < 0.1, 3] = np.nan
    X[:, 5] = 1.0
    names = [f"f{i}" for i in range(9)]
    w = rng.rand(5000).astype(np.float32)
    one = quality.build_training_sketch(X, names, weight=w, threads=1)
    many = quality.build_training_sketch(X, names, weight=w, threads=8)
    assert one == many and list(one["features"]) == names
    # rows on the device, the first `rows` of them: the same payload as the
    # host's copy of those rows
    cut = quality.build_training_sketch(jnp.asarray(X), names, weight=w[:4000], rows=4000)
    assert cut == quality.build_training_sketch(X[:4000], names, weight=w[:4000])


# -- (8) the subscope: a second naming, a map of its own --------------------


def test_subscope_has_a_map_of_its_own_and_is_in_the_cache_key():
    from ytklearn_tpu import obs
    from ytklearn_tpu.obs import scopes

    obs.configure(enabled=True)

    def make(sub):
        def wide_subscope_probe(x):
            with scopes.scope("test.wide.outer"):
                y = jnp.sin(x) * 2.0
                if sub:
                    with scopes.subscope("test.wide.outer.part"):
                        y = y + jnp.cumsum(x)
                else:
                    y = y + jnp.cumsum(x)
            return y

        return wide_subscope_probe

    x = jnp.arange(8.0)
    with_sub = jax.jit(make(True)).lower(x)
    without = jax.jit(make(False)).lower(x)
    c = scopes.compile_lowered(with_sub)
    scopes.compile_lowered(without)
    np.testing.assert_allclose(np.asarray(c(x)), np.sin(np.arange(8.0)) * 2 + np.cumsum(np.arange(8.0)))
    digest = lambda low: re.search(r'ytk_scopes = "([0-9a-f]+)"', str(low.compiler_ir("stablehlo")))  # noqa: E731
    assert digest(with_sub).group(1) != digest(without).group(1)
    # the scope map knows the scope and not the subscope
    name = "jit_wide_subscope_probe"
    assert set(scopes.scope_map()[name].values()) == {"test.wide.outer"}
    assert scopes.innermost_scope("jit(f)/test.wide.outer/test.wide.outer.part/add") == "test.wide.outer"
    assert scopes.innermost_scope(
        "jit(f)/test.wide.outer/test.wide.outer.part/add", scopes._SUBSCOPES
    ) == "test.wide.outer.part"
