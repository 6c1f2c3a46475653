"""Device growth engine vs the host reference implementation.

The device engine (gbdt/engine.py, one XLA program per tree) must grow
IDENTICAL trees to the host per-level/per-split loop on the same data:
level policy exactly, loss policy exactly at wave=1 (strict best-first);
wave>1 relaxes pop granularity and is checked for quality, not identity.
"""

import os
import signal

import numpy as np
import pytest

from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams, ModelParams
from ytklearn_tpu.gbdt import trainer as trainer_mod
from ytklearn_tpu.gbdt.data import GBDTData
from ytklearn_tpu.gbdt.engine import GrowSpec
from ytklearn_tpu.gbdt.trainer import GBDTTrainer
from ytklearn_tpu.resilience.preempt import Preempted


def _data(n=1200, F=6, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    logit = X[:, 0] * X[:, 1] + np.sin(2 * X[:, 2]) + 0.5 * (X[:, 3] > 0)
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return GBDTData(
        X=X,
        y=y,
        weight=np.ones(n, np.float32),
        n_real=n,
        feature_names=[str(i) for i in range(F)],
    )


def _params(tmp_path, policy, **over):
    kw = dict(
        round_num=3,
        max_depth=4 if policy == "level" else 20,
        max_leaf_cnt=12,
        tree_grow_policy=policy,
        learning_rate=0.3,
        min_child_hessian_sum=1.0,
        loss_function="sigmoid",
        eval_metric=["auc"],
        approximate=[ApproximateSpec(max_cnt=32)],
        model=ModelParams(data_path=str(tmp_path / "m.model"), dump_freq=0),
    )
    kw.update(over)
    return GBDTParams(**kw)


def _tree_sig(t):
    """Structural signature. Leaf values are rounded to 4dp: the engine
    derives sibling histograms by pool subtraction (the reference's own
    HistogramPool trick) while the host level path sums every node
    directly, so G/H sums differ in the last f32 ULP."""
    return [
        (
            t.feat[i],
            round(float(t.split[i]), 5),
            t.left[i],
            t.right[i],
            round(t.leaf_value[i], 4),
        )
        for i in range(t.n_nodes())
    ]


@pytest.mark.parametrize("policy", ["level", "loss"])
def test_engine_matches_host(tmp_path, policy):
    data = _data()
    p_host = _params(tmp_path / "host", policy)
    p_dev = _params(tmp_path / "dev", policy)
    (tmp_path / "host").mkdir()
    (tmp_path / "dev").mkdir()

    res_h = GBDTTrainer(p_host, engine="host").train(train=_data())
    res_d = GBDTTrainer(
        p_dev, engine="device", wave=1, hist_precision="f32"
    ).train(train=_data())

    assert len(res_h.model.trees) == len(res_d.model.trees)
    for th, td in zip(res_h.model.trees, res_d.model.trees):
        assert _tree_sig(th) == _tree_sig(td)
        np.testing.assert_allclose(th.hess_sum, td.hess_sum, rtol=1e-4, atol=1e-4)
        assert th.sample_cnt == td.sample_cnt
    assert res_d.train_loss == pytest.approx(res_h.train_loss, rel=1e-4)


def test_engine_wide_wave_quality(tmp_path):
    """Batched best-first (wave=4 at 32 leaves, the same ~1/8 pop ratio the
    TPU path uses at 16/255): trees may differ from strict best-first, but
    fit quality must stay equivalent."""
    p1 = _params(tmp_path / "w1", "loss", round_num=5, max_leaf_cnt=32)
    p4 = _params(tmp_path / "w4", "loss", round_num=5, max_leaf_cnt=32)
    (tmp_path / "w1").mkdir()
    (tmp_path / "w4").mkdir()
    res1 = GBDTTrainer(p1, engine="device", wave=1).train(train=_data())
    res4 = GBDTTrainer(p4, engine="device", wave=4).train(train=_data())
    assert res4.train_metrics["auc"] == pytest.approx(
        res1.train_metrics["auc"], abs=0.015
    )
    assert res4.train_loss == pytest.approx(res1.train_loss, rel=0.05)


def test_engine_test_set_and_budget(tmp_path):
    """Test rows route through the same trees; leaf budget respected."""
    p = _params(tmp_path, "loss", round_num=4, max_leaf_cnt=7)
    res = GBDTTrainer(p, engine="device", wave=4).train(
        train=_data(), test=_data(seed=11)
    )
    for t in res.model.trees:
        assert t.leaf_cnt() <= 7
    assert res.test_loss is not None
    assert res.test_loss < 0.6  # learned signal transfers
    assert [r["round"] for r in res.round_log] == [0, 1, 2, 3]
    assert res.round_log[-1]["train_loss"] < res.round_log[0]["train_loss"]


def test_engine_multiclass_softmax(tmp_path):
    rng = np.random.RandomState(2)
    n, F, K = 900, 5, 3
    X = rng.randn(n, F).astype(np.float32)
    cls = (X[:, 0] > 0.3).astype(int) + (X[:, 1] > 0.1).astype(int)
    y = np.zeros((n, K), np.float32)
    y[np.arange(n), cls] = 1.0
    data = GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[str(i) for i in range(F)],
    )
    p = _params(
        tmp_path, "level", round_num=3, loss_function="softmax", class_num=K,
        eval_metric=["confusion_matrix"],
    )
    res = GBDTTrainer(p, engine="device").train(train=data)
    assert len(res.model.trees) == 3 * K
    assert res.train_metrics["confusion_matrix"] > 0.8


def test_int8_hist_exact_on_integer_grads():
    """With integer-valued g/h at max-abs 127 the int8 quantization is
    lossless, so hist_wave at int8 must equal hist_wave at f32 exactly."""
    import jax.numpy as jnp

    from ytklearn_tpu.gbdt.hist import hist_wave

    rng = np.random.RandomState(0)
    n, F, B = 8192, 4, 16
    bins_t = jnp.asarray(rng.randint(0, B, size=(F, n)).astype(np.int32))
    g_int = rng.randint(-127, 128, n).astype(np.float32)
    h_int = rng.randint(0, 128, n).astype(np.float32)
    pos = jnp.asarray(rng.randint(-1, 3, n).astype(np.int32))
    ids = jnp.asarray(np.arange(3, dtype=np.int32))

    ref = np.asarray(
        hist_wave(bins_t, pos, jnp.asarray(g_int), jnp.asarray(h_int), ids, B,
                  precision="f32", kernels="dense")
    )
    got = np.asarray(
        hist_wave(
            bins_t, pos,
            jnp.asarray(g_int), jnp.asarray(h_int),
            ids, B, precision="int8", kernels="dense",
        )
    ).astype(np.float32)
    np.testing.assert_array_equal(ref, got)


def test_int8_engine_quality_close_to_bf16(tmp_path):
    """int8-quantized histograms must not visibly hurt model quality."""
    data = _data(n=4000)
    p = _params(tmp_path, "loss", round_num=6, max_leaf_cnt=24)
    res_ref = GBDTTrainer(p, engine="device", hist_precision="f32").train(train=data)
    res_q = GBDTTrainer(p, engine="device", hist_precision="int8").train(train=data)
    assert abs(res_q.train_metrics["auc"] - res_ref.train_metrics["auc"]) < 0.01
    assert res_q.train_loss == pytest.approx(res_ref.train_loss, rel=0.05)


def test_engine_sharded_int8_matches_single(tmp_path, mesh8):
    """mesh>1 runs the SAME growth program under shard_map (per-shard hist
    kernels + psum_scatter feature-slice ownership + pargmax best-split
    merge, r3 VERDICT #1). In int8 mode the histogram sums are exact i32,
    so the 8-device program must grow IDENTICAL trees to one device —
    including feature-axis padding (F=6 over 8 devices -> 2 devices own
    only padded features)."""
    p1 = _params(tmp_path / "one", "loss", round_num=3, max_leaf_cnt=12)
    p8 = _params(tmp_path / "eight", "loss", round_num=3, max_leaf_cnt=12)
    (tmp_path / "one").mkdir()
    (tmp_path / "eight").mkdir()
    res1 = GBDTTrainer(
        p1, engine="device", wave=4, hist_precision="int8"
    ).train(train=_data(n=1600))
    res8 = GBDTTrainer(
        p8, mesh=mesh8, engine="device", wave=4, hist_precision="int8"
    ).train(train=_data(n=1600))
    assert len(res8.model.trees) == len(res1.model.trees)
    for t1, t8 in zip(res1.model.trees, res8.model.trees):
        assert _tree_sig(t1) == _tree_sig(t8)
        assert t1.sample_cnt == t8.sample_cnt
    assert res8.train_loss == pytest.approx(res1.train_loss, rel=1e-5)


@pytest.mark.parametrize("policy", ["level", "loss"])
def test_engine_sharded_f32_quality(tmp_path, mesh8, policy):
    """f32 mode: per-shard partial sums reorder float accumulation, so
    trees may differ in last-ULP ties — fit quality must be equivalent."""
    p1 = _params(tmp_path / "one", policy, round_num=3)
    p8 = _params(tmp_path / "eight", policy, round_num=3)
    (tmp_path / "one").mkdir()
    (tmp_path / "eight").mkdir()
    res1 = GBDTTrainer(
        p1, engine="device", wave=4, hist_precision="f32"
    ).train(train=_data(n=1600))
    res8 = GBDTTrainer(
        p8, mesh=mesh8, engine="device", wave=4, hist_precision="f32"
    ).train(train=_data(n=1600))
    assert res8.train_loss == pytest.approx(res1.train_loss, rel=1e-3)
    assert res8.train_metrics["auc"] == pytest.approx(
        res1.train_metrics["auc"], abs=0.005
    )


def test_partitioned_hist_matches_full_scan(tmp_path, monkeypatch):
    """Leaf-partitioned histogram passes (GrowSpec.ladder — per-wave row
    compaction + gathered-budget kernels) must grow IDENTICAL trees to the
    full-scan path: the same rows enter every histogram, and in int8 mode
    the i32 sums are order-independent, so equality is exact."""
    data = _data(n=3000)
    p_on = _params(tmp_path / "on", "loss", round_num=3, max_leaf_cnt=24)
    p_off = _params(tmp_path / "off", "loss", round_num=3, max_leaf_cnt=24)
    (tmp_path / "on").mkdir()
    (tmp_path / "off").mkdir()
    tr_on = GBDTTrainer(p_on, engine="device", wave=8, hist_precision="int8")
    res_on = tr_on.train(train=data)
    # no ladder, no partitioned pass (whichever family this platform is)
    monkeypatch.setattr(
        trainer_mod, "LADDER", {k: () for k in trainer_mod.LADDER}
    )
    tr_off = GBDTTrainer(p_off, engine="device", wave=8, hist_precision="int8")
    res_off = tr_off.train(train=data)
    assert tr_on.time_stats["partition"] and not tr_off.time_stats["partition"]
    assert len(res_on.model.trees) == len(res_off.model.trees)
    for t_on, t_off in zip(res_on.model.trees, res_off.model.trees):
        assert _tree_sig(t_on) == _tree_sig(t_off)
        assert t_on.sample_cnt == t_off.sample_cnt
    assert res_on.train_loss == pytest.approx(res_off.train_loss, rel=1e-6)


def test_partitioned_hist_sharded(tmp_path, mesh8):
    """Partitioned hist under shard_map: shard-local budget choice with the
    psum_scatter outside the ladder conds — 8-device trees must still equal
    the single-device int8 trees exactly."""
    p1 = _params(tmp_path / "one", "loss", round_num=2, max_leaf_cnt=16)
    p8 = _params(tmp_path / "eight", "loss", round_num=2, max_leaf_cnt=16)
    (tmp_path / "one").mkdir()
    (tmp_path / "eight").mkdir()
    res1 = GBDTTrainer(
        p1, engine="device", wave=4, hist_precision="int8"
    ).train(train=_data(n=2560))
    tr8 = GBDTTrainer(
        p8, mesh=mesh8, engine="device", wave=4, hist_precision="int8"
    )
    res8 = tr8.train(train=_data(n=2560))
    assert tr8.time_stats["partition"]  # 320 rows a shard: one 128-row rung
    for t1, t8 in zip(res1.model.trees, res8.model.trees):
        assert _tree_sig(t1) == _tree_sig(t8)


# -- which histogram kernel runs: one table, no environment ----------------

_HIGGS_ROWS = 10_502_144  # gbdt_higgs.train: 10.5M rows padded to bm 16384


def _rung_spec(**kw):
    base = dict(
        F=28, B=256, max_nodes=509, wave=64, policy="loss", max_depth=0,
        max_leaves=255, lr=0.1, l1=0.0, l2=0.0, min_h=100.0, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0,
    )
    base.update(kw)
    return GrowSpec(**base)


def _family(kernels):
    """What _grow_spec hands the engine for one implementation family."""
    return dict(
        kernels=kernels, ladder=trainer_mod.LADDER[kernels],
        fused_max_rows=trainer_mod.FUSED_MAX_ROWS,
    )


@pytest.mark.parametrize(
    "kw, n, want",
    [
        # the benchmark cell's shape on the chip: ceil(n/256) and ceil(n/64)
        # up to bm_g 1024, both under 2^18 -> two fused rungs
        (_family("pallas"), _HIGGS_ROWS,
         ((41_984, "fused"), (164_864, "fused"))),
        # the CPU family: XLA gathers at n/32 and n/8, in units of 128
        (_family("dense"), 16_384, ((512, "xla"), (2_048, "xla"))),
        # no ladder, no partitioned pass
        (dict(kernels="pallas", ladder=()), _HIGGS_ROWS, ()),
        # fused_max_rows 0: every rung takes the XLA gather, in units of bm
        ({**_family("pallas"), "fused_max_rows": 0}, _HIGGS_ROWS,
         ((49_152, "xla"), (180_224, "xla"))),
        # a rung over the cap falls back to the XLA gather, the other fuses
        ({**_family("pallas"), "fused_max_rows": 100_000}, _HIGGS_ROWS,
         ((41_984, "fused"), (180_224, "xla"))),
        # a budget that is not smaller than n is no rung
        (dict(kernels="dense", ladder=(1, 8)), 256, ((128, "xla"),)),
        # two divisors that round to one budget give one rung
        (dict(kernels="dense", ladder=(16, 32)), 1_024, ((128, "xla"),)),
        # the dense family fuses only through the interpreter (tests)
        (dict(kernels="dense", ladder=(4,), bm_g=512, fused_interpret=True),
         4_096, ((1_024, "fused"),)),
    ],
)
def test_rung_table(kw, n, want):
    assert _rung_spec(**kw).rungs(n) == want


_RETIRED = {
    "YTK_PARTITION": "0", "YTK_NO_PARTITION": "1", "YTK_LADDER": "2,4",
    "YTK_FUSED": "0", "YTK_FUSED_MAX_ROWS": "1",
}


def test_grow_spec_ignores_retired_knobs(tmp_path, monkeypatch):
    """The five environment variables that used to steer the kernel choice
    are neither read nor declared: the choice is the platform's."""
    import jax

    from ytklearn_tpu.config import knobs

    tr = GBDTTrainer(_params(tmp_path, "loss"), engine="device")
    spec = tr._grow_spec(28, 256)
    family = "pallas" if jax.default_backend() == "tpu" else "dense"
    assert (spec.kernels, spec.ladder, spec.fused_max_rows) == (
        family, trainer_mod.LADDER[family], trainer_mod.FUSED_MAX_ROWS,
    )
    for name, val in _RETIRED.items():
        monkeypatch.setenv(name, val)
    assert tr._grow_spec(28, 256) == spec
    for name in _RETIRED:
        assert name not in knobs.KNOBS
        with pytest.raises(KeyError, match="undeclared knob"):
            knobs.get_raw(name)


def test_benchmark_contract(tmp_path):
    """What perfbench/families/gbdt.py depends on, held at a toy size: the
    constructor's keywords, train(train=, test=), the three methods it
    wraps on the instance, and time_stats["preprocess"]."""
    from ytklearn_tpu.io.fs import LocalFileSystem

    p = _params(tmp_path, "loss", round_num=4, max_leaf_cnt=8)
    tr = GBDTTrainer(p, mesh=None, fs=LocalFileSystem(), hist_precision="int8")
    seen = {"probe": 0, "calls": 0, "rounds": 0, "preempt": []}
    orig_probe, orig_rounds = tr._probe_compile, tr._run_rounds
    orig_preempt = tr._preempt_checkpoint

    def probe_compile(jit_round, carry, data, start_round):
        compiled = orig_probe(jit_round, carry, data, start_round)
        seen["probe"] += 1

        def counted(carry, rnd, key, data):
            seen["calls"] += 1
            if seen["calls"] == 2:
                os.kill(os.getpid(), signal.SIGTERM)  # the harness's stop
            return compiled(carry, rnd, key, data)

        return counted

    def run_rounds(*a, **kw):
        seen["rounds"] += 1
        return orig_rounds(*a, **kw)

    def preempt_checkpoint(model, bufs, bins, names, rnd):
        seen["preempt"].append((rnd, tuple(np.asarray(bufs["wlog"]).shape)))
        return orig_preempt(model, bufs, bins, names, rnd)

    tr._probe_compile = probe_compile
    tr._run_rounds = run_rounds
    tr._preempt_checkpoint = preempt_checkpoint
    with pytest.raises(Preempted):
        tr.train(train=_data(), test=_data(seed=11))
    assert seen["probe"] == 1 and seen["rounds"] == 1 and seen["calls"] == 2
    # stopped at the boundary after the second round, wave log in hand
    assert [r for r, _ in seen["preempt"]] == [2]
    assert seen["preempt"][0][1][0] == 4 and seen["preempt"][0][1][2] == 5
    assert tr.time_stats["preprocess"] > 0
    # the gauges perfbench/metrics/{hist_pool_gib,hist_part_roofline}.py and
    # docs/observability.md name, published on the stop path too: what the
    # width chose and what the partitioned passes needed
    for k in ("features", "hist_pool_bytes", "route_kernel", "packed_tiles",
              "rungs_fused", "rungs_xla", "trees_logged", "hist_part_passes",
              "hist_part_rows_scanned", "hist_part_rows_needed",
              "leaf_lookup_kernel", "hist_factored_passes"):
        assert k in tr.time_stats, k
    assert tr.time_stats["features"] == 6
    assert tr.time_stats["hist_pool_bytes"] == 15 * 6 * 32 * 3 * 4
    assert tr.time_stats["trees_logged"] == 2

    # a run that ends by itself hands the carry back through _run_rounds
    p2 = _params(tmp_path / "b", "loss", round_num=2, max_leaf_cnt=8)
    (tmp_path / "b").mkdir()
    tr2 = GBDTTrainer(p2, mesh=None, fs=LocalFileSystem())
    out = {}
    orig2 = tr2._run_rounds

    def run_rounds2(*a, **kw):
        out["carry"] = orig2(*a, **kw)
        return out["carry"]

    tr2._run_rounds = run_rounds2
    tr2.train(train=_data(), test=_data(seed=11))
    carry = out["carry"]
    assert np.asarray(carry[2]["wlog"]).shape[0] == 2
    assert np.asarray(carry[3]).shape == (2,)
    assert np.all(np.asarray(carry[3]) > 0)


@pytest.mark.parametrize("engine", ["host", "auto"])
def test_precise_lad_reaches_host_engine(tmp_path, monkeypatch, engine):
    """engine="host", and "auto" with precise LAD refinement, train through
    gbdt/host_engine.py's train_host on the trainer."""
    from ytklearn_tpu.gbdt import host_engine

    calls = []
    orig = host_engine.train_host

    def spy(trainer, train=None, test=None):
        calls.append(trainer)
        return orig(trainer, train, test)

    monkeypatch.setattr(trainer_mod, "train_host", spy)
    p = _params(
        tmp_path, "level", round_num=2, loss_function="l1",
        eval_metric=[], lad_refine_appr=False,
    )
    tr = GBDTTrainer(p, engine=engine)
    res = tr.train(train=_data())
    assert calls == [tr] and tr.engine == "host"
    assert len(res.model.trees) == 2
