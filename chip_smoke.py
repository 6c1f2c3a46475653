"""chip_smoke.py — does GBDT training, started the way a user starts it, run
on the TPU at the Higgs acceptance width? The quickest proof that the system
still starts on the chip.

One process; no CPU mode: it fails unless `jax.default_backend()` is `tpu`.
Stages run in order and the first failure ends the run non-zero (no stage's
exception is caught):

  A  `ytklearn_tpu.cli.train_main(["gbdt", experiment/higgs/local_gbdt.conf,
     ...])` in-process on a seeded 2^20 + 2^16 row text file: native ingest,
     falling loss, 8 trees, the fused+partitioned program un-downgraded, no
     recompiles after the first sync, dumped model == device scores; the
     round program looked its leaf values up through the one-pass kernel,
     and that kernel equals `leaf[pos]` bit for bit at 2^20 rows, 509 nodes.
  B  the same trainer construction on 10.5M + 500k device-generated rows,
     5 rounds: binning, histogram pool and round program at the real n.
  C  int8 histograms are exact, so the full-scan, XLA-gather and fused
     programs must grow the bit-identical tree at F=28/B=256/wave 64; plus
     the toy-width tree of tests/data/crosscheck_tree.json.
  D  with >= 4 chips: stage A on a 4-device mesh (shards on four devices) and
     the 4-device int8 tree == the stage C single-chip tree.

Seconds printed along the way are bring-up observations, not metrics. The
last stdout line is {"ok": true, "device": {...}}. Artifacts go under
chiprun_out/chip_smoke/. To observe the trainer's device scores and inputs
the smoke wraps two GBDTTrainer methods with recorders; they change nothing.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CONF = os.path.join(ROOT, "experiment", "higgs", "local_gbdt.conf")
F, B, WAVE, LEAVES = 28, 256, 64, 255  # the Higgs acceptance width
N_A, NT_A, ROUNDS_A = 1 << 20, 1 << 16, 8
N_B, NT_B, ROUNDS_B = 10_500_000, 500_000, 5
SCORE_ROWS = 4096
# device scores are an f32 running sum of 8 leaf values of magnitude <~1;
# the predictor sums the same dumped f32 leaves in f64
SCORE_ATOL = 1e-5


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def record_calls(cls, name: str, sink: list):
    """Wrap cls.name so each call appends (self, args, result) to sink."""
    orig = getattr(cls, name)

    def wrapper(self, *args, **kw):
        res = orig(self, *args, **kw)
        sink.append((self, args, res))
        return res

    setattr(cls, name, wrapper)
    return orig


def write_ytk(path: str, X, y) -> None:
    """weight###label###name:value,... (experiment/higgs/higgs2ytklearn.py
    format, feature names = column indices); %.9g round-trips float32."""
    fmt = "1###%d###" + ",".join(f"{j}:%.9g" for j in range(X.shape[1])) + "\n"
    with open(path, "w") as f:
        for lo in range(0, len(X), 1 << 16):
            rows = X[lo:lo + (1 << 16)].tolist()
            labs = y[lo:lo + (1 << 16)].tolist()
            f.write("".join(fmt % (int(l), *r) for l, r in zip(labs, rows)))


def cli_train(devices: int, tag: str, train_path: str, test_path: str):
    """One `train_main` run; returns (trainer, dev_inputs, result, scores_t,
    obs snapshot, model path)."""
    from ytklearn_tpu import obs
    from ytklearn_tpu.cli import train_main
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    prepped, finalized = [], []
    orig_p = record_calls(GBDTTrainer, "_prep_device_inputs", prepped)
    orig_f = record_calls(GBDTTrainer, "_finalize_device", finalized)
    model_path = os.path.join(OUT, f"{tag}.model")
    try:
        rc = train_main([
            "gbdt", CONF, "--devices", str(devices),
            "--set", f"data.train.data_path={train_path}",
            "--set", f"data.test.data_path={test_path}",
            "--set", f"model.data_path={model_path}",
            "--set", f"model.dict_path={os.path.join(OUT, tag + '.dict')}",
            "--set", "model.feature_importance_path="
            + os.path.join(OUT, tag + ".importance"),
            "--set", f"optimization.round_num={ROUNDS_A}",
        ])
    finally:
        GBDTTrainer._prep_device_inputs = orig_p
        GBDTTrainer._finalize_device = orig_f
    check(rc == 0, f"train_main returned {rc}")
    trainer, fargs, res = finalized[-1]
    scores_t = fargs[5]  # _finalize_device(model, bins, scores, y, w, scores_t, ...)
    return trainer, prepped[-1][2], res, scores_t, obs.snapshot(), model_path


def check_training(res, snap, rounds: int, what: str) -> None:
    import numpy as np

    losses = [r["train_loss"] for r in res.round_log]
    print(f"{what}: train loss by round {[round(v, 5) for v in losses]}")
    check(len(losses) == rounds, f"{what}: {len(losses)} rounds logged")
    check(all(np.isfinite(losses)), f"{what}: non-finite loss")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"{what}: train loss does not fall round over round")
    check(losses[-1] < math.log(2), f"{what}: final loss {losses[-1]} >= log 2")
    check(len(res.model.trees) == rounds, f"{what}: {len(res.model.trees)} trees")
    leaves = [t.leaf_cnt() for t in res.model.trees]
    check(all(2 <= c <= LEAVES for c in leaves), f"{what}: leaf counts {leaves}")
    g, c = snap["gauges"], snap["counters"]
    check(g.get("gbdt.stat.fused") == 1.0 and g.get("gbdt.stat.partition") == 1.0,
          f"{what}: not the fused+partitioned program: "
          f"fused={g.get('gbdt.stat.fused')} partition={g.get('gbdt.stat.partition')}")
    check(g.get("gbdt.stat.leaf_lookup_kernel") == 1.0,
          f"{what}: the round program's leaf lookup is not the kernel: "
          f"leaf_lookup_kernel={g.get('gbdt.stat.leaf_lookup_kernel')}")
    down = {k: v for k, v in c.items()
            if (k.startswith("gbdt.downgrade") or k == "gbdt.efb.downgrade") and v}
    check(not down, f"{what}: downgrade counters {down}")
    check(not c.get("compile.retraces.unexpected"),
          f"{what}: {c.get('compile.retraces.unexpected')} compiles after the first sync")
    print(f"{what}: {rounds} trees, leaves {min(leaves)}..{max(leaves)}, "
          f"fused=1 partition=1 leaf_lookup_kernel=1, downgrades 0, "
          f"unexpected compiles 0")


def wave_summary(trainer, what: str) -> None:
    """Which histogram programs the run executed, from the engine's wave log
    (rows_scanned per pass: the full n, or a partition budget R)."""
    wl = trainer.wave_log
    used = wl[wl[..., 3] > 0]
    budgets = sorted({int(r) for r in used[:, 0]})
    part = int((used[:, 0] < used[:, 0].max()).sum())
    print(f"{what}: {len(used)} histogram passes over {wl.shape[0]} trees, "
          f"{part} of them partitioned (row budgets {budgets})")


def check_leaf_lookup(what: str) -> None:
    """route.leaf_values' kernel against XLA's gather and numpy at the
    acceptance tree's size: every node id hit, leaf values of every sign and
    magnitude; equality is of bits."""
    import jax.numpy as jnp
    import numpy as np

    from ytklearn_tpu.gbdt.hist import BM_DEFAULT
    from ytklearn_tpu.gbdt.route import leaf_values

    M = 2 * LEAVES - 1
    rng = np.random.RandomState(11)
    leaf = (rng.randn(M) * np.exp(6.0 * rng.randn(M))).astype(np.float32)
    leaf[:6] = np.array([-0.0, 0.0, 1e-42, -1.1754944e-38, 3e38, -3e38], np.float32)
    pos = rng.randint(0, M, size=N_A).astype(np.int32)
    pos[:M] = np.arange(M)
    pos = rng.permutation(pos)
    got, gather = (
        np.asarray(leaf_values(jnp.asarray(leaf), jnp.asarray(pos),
                               kernels=k, bm=BM_DEFAULT)).view(np.uint32)
        for k in ("pallas", "dense"))
    want = leaf[pos].view(np.uint32)
    check(np.array_equal(got, want) and np.array_equal(gather, want),
          f"{what}: leaf_values differs from leaf[pos] in "
          f"{int((got != want).sum())} (kernel) / "
          f"{int((gather != want).sum())} (gather) of {N_A} rows")
    print(f"{what}: gbdt_leaf_values == leaf[pos] bit for bit, {N_A} rows, "
          f"{M} nodes")


def check_model_scores(model_path: str, X_test, scores_t, what: str) -> None:
    """Dumped model text, loaded by the serving predictor, vs the trainer's
    device scores on the first SCORE_ROWS test rows."""
    import numpy as np

    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.predict import create_predictor

    cfg = hocon.set_path(hocon.load(CONF), "model.data_path", model_path)
    predictor = create_predictor("gbdt", cfg)
    rows = [
        {str(j): float(v) for j, v in enumerate(r)} for r in X_test[:SCORE_ROWS]
    ]
    host = predictor.batch_scores(rows)
    dev = np.asarray(scores_t[:SCORE_ROWS], np.float64)
    check(np.isfinite(dev).all() and dev.shape == host.shape,
          f"{what}: device scores shape {dev.shape} / non-finite")
    err = float(np.abs(host - dev).max())
    print(f"{what}: predictor vs device scores on {SCORE_ROWS} test rows: "
          f"max |diff| {err:.2e} (atol {SCORE_ATOL})")
    check(err <= SCORE_ATOL, f"{what}: dumped model disagrees with device scores")


def stage_a(dev_line: str, paths):
    import numpy as np

    import bench
    from ytklearn_tpu.io import native

    t0 = time.time()
    train, test = bench._gen_gbdt(N_A, NT_A, F)  # seeded planted signal
    X, y = np.asarray(train.X), np.asarray(train.y)
    Xt, yt = np.asarray(test.X), np.asarray(test.y)
    del train, test
    write_ytk(paths[0], X, y)
    write_ytk(paths[1], Xt, yt)
    print(f"stage A: wrote {N_A}+{NT_A} rows x {F} "
          f"({os.path.getsize(paths[0]) >> 20} MiB) in {time.time() - t0:.1f}s")

    t0 = time.time()
    trainer, dd, res, scores_t, snap, model_path = cli_train(1, "a", *paths)
    wall = time.time() - t0
    so = glob.glob(os.path.join(ROOT, "native", "build", "libytkparse-*.so"))
    check(bool(so) and native.native_available(),
          "stage A: ingest did not go through the native parser")
    check(snap["counters"].get("ingest.rows") == N_A + NT_A,
          f"stage A: ingested {snap['counters'].get('ingest.rows')} rows")
    check_training(res, snap, ROUNDS_A, "stage A")
    wave_summary(trainer, "stage A")
    check_model_scores(model_path, Xt, scores_t, "stage A")
    check_leaf_lookup("stage A")
    ts = trainer.time_stats
    print(f"stage A [{dev_line}]: wall {wall:.1f}s = load {ts['load']:.1f} + "
          f"preprocess {ts['preprocess']:.1f} + train {ts['train']:.1f} + "
          f"finalize {ts['finalize']:.1f} + ingest/compile/other "
          f"{wall - ts['load'] - ts['preprocess'] - ts['train'] - ts['finalize']:.1f}")
    return Xt


def stage_b(dev_line: str) -> None:
    import jax

    import bench
    from ytklearn_tpu import obs
    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.config.params import GBDTParams
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer
    from ytklearn_tpu.io.fs import create_filesystem

    cfg = hocon.load(CONF)
    cfg = hocon.set_path(cfg, "optimization.round_num", ROUNDS_B)
    for key, name in (("model.data_path", "b.model"), ("model.dict_path", "b.dict"),
                      ("model.feature_importance_path", "b.importance")):
        cfg = hocon.set_path(cfg, key, os.path.join(OUT, name))
    p = GBDTParams.from_config(cfg)
    fs = create_filesystem(str(cfg.get("fs_scheme", "local")))
    t0 = time.time()
    train, test = bench._gen_gbdt(N_B, NT_B, F)
    t_gen = time.time() - t0
    t0 = time.time()
    trainer = GBDTTrainer(p, mesh=None, fs=fs)  # cli._train_once's construction
    res = trainer.train(train=train, test=test)
    wall = time.time() - t0
    snap = obs.snapshot()
    check_training(res, snap, ROUNDS_B, "stage B")
    wave_summary(trainer, "stage B")
    ts = trainer.time_stats
    mem = jax.devices()[0].memory_stats() or {}
    print(f"stage B [{dev_line}] {N_B}+{NT_B} rows: data gen {t_gen:.1f}s, "
          f"preprocess {ts['preprocess']:.1f}s, compile/other "
          f"{wall - ts['load'] - ts['preprocess'] - ts['train'] - ts['finalize']:.1f}s, "
          f"train {ts['train']:.1f}s = {ts['train'] / ROUNDS_B:.2f}s per tree, "
          f"finalize {ts['finalize']:.1f}s, peak HBM "
          f"{mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
          f"(bring-up observations, not metrics)")


def full_width_case():
    """Pre-binned 2^20 x 28 x 256 case with planted signal and host f32
    grads, so every program sees bit-identical inputs."""
    import numpy as np

    rng = np.random.RandomState(7)
    n = N_A
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    b = bins.astype(np.float32) / B
    logit = (3.0 * b[:, 0] * b[:, 1] + np.sin(6.0 * b[:, 2])
             + 0.8 * (b[:, 3] > 0.5) - 2.0 * b[:, 4] ** 2)
    y = (logit + 0.5 * rng.randn(n) > 1.0).astype(np.float32)
    p = (1.0 / (1.0 + np.exp(-(logit - 1.0)))).astype(np.float32)
    g = (p - y).astype(np.float32)
    h = np.maximum(p * (1 - p), 1e-6).astype(np.float32)
    return np.ascontiguousarray(bins.T), g, h


LADDER_C = (4, 16)  # stage C's budget divisors, see full_width_spec


def full_width_spec(ladder, fused_max_rows):
    from ytklearn_tpu.gbdt.engine import GrowSpec

    # what GBDTTrainer._grow_spec builds for local_gbdt.conf on TPU, in int8
    # mode — except the ladder. With 255 leaves grown 64 at a time the last
    # waves still need ~n/9 rows, so the shipped (64, 256) budgets are never
    # reached (seen on the chip: every pass scanned all n rows) and the
    # gather kernels would compile but not execute. (4, 16) are budgets the
    # late waves do reach; n/4 = 2^18 is the largest the fused kernel takes.
    return GrowSpec(
        F=F, B=B, max_nodes=2 * LEAVES - 1, wave=WAVE, policy="loss",
        max_depth=-1, max_leaves=LEAVES, lr=0.1, l1=0.0, l2=0.0, min_h=100.0,
        max_abs=0.0, min_split_loss=0.0, min_split_samples=-1.0,
        precision="int8", kernels="pallas", ladder=ladder,
        fused_max_rows=fused_max_rows,
    )


def grow_full_width(case, spec, devices=None):
    """Grow one tree; returns ({field: np.ndarray}, wave log)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ytklearn_tpu.gbdt.engine import make_grow_tree

    bins_t, g, h = case
    n = bins_t.shape[1]
    mesh = None if devices is None else Mesh(np.asarray(devices), ("data",))
    grow = make_grow_tree(spec, mesh=mesh)
    args = (jnp.asarray(bins_t), jnp.ones((n,), bool), jnp.asarray(g),
            jnp.asarray(h), jnp.ones((F,), bool))
    if mesh is not None:
        specs = (P(None, "data"), P("data"), P("data"), P("data"), P("data"))
        args = tuple(
            jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(args, specs)
        )
    t0 = time.time()
    tr, _pos, _aux, wlog = jax.jit(lambda *a: grow(*a))(*args)
    tree = {k: np.asarray(v) for k, v in tr._asdict().items()}
    return tree, np.asarray(wlog), time.time() - t0


def same_tree(a: dict, b: dict) -> bool:
    import numpy as np

    return all(np.array_equal(a[k], b[k]) for k in a)


def stage_c(dev_line: str):
    import numpy as np

    from scripts.cross_check import LADDER, grow_single, make_case
    from ytklearn_tpu.gbdt.trainer import FUSED_MAX_ROWS

    case = full_width_case()
    trees = {}
    for name, ladder, fused_max, impls in (
            ("full-scan", (), 0, set()),
            ("xla-gather", LADDER_C, 0, {"xla"}),
            ("fused", LADDER_C, FUSED_MAX_ROWS, {"fused"})):
        spec = full_width_spec(ladder, fused_max)
        # the table the engine builds its passes from names the kernels
        rungs = spec.rungs(N_A)
        check({impl for _, impl in rungs} == impls,
              f"stage C: {name} builds rungs {rungs}")
        trees[name], wlog, secs = grow_full_width(case, spec)
        used = wlog[wlog[:, 3] > 0]
        budgets = sorted({int(r) for r in used[:, 0]})
        print(f"stage C [{dev_line}] {name}: {int(trees[name]['n_nodes'])} nodes, "
              f"{len(used)} histogram passes over row budgets {budgets}, "
              f"compile+run {secs:.1f}s")
        if ladder:  # the partitioned phases must really have run
            check(len(budgets) > 1, f"stage C: {name} never left the full scan")
    ref = trees["full-scan"]
    check(int(ref["n_nodes"]) == 2 * LEAVES - 1,
          f"stage C: full-width tree has {int(ref['n_nodes'])} nodes")
    for name in ("xla-gather", "fused"):
        check(same_tree(ref, trees[name]),
              f"stage C: {name} tree differs from the full-scan tree")
    print(f"stage C: full-scan == xla-gather == fused, bit-identical at "
          f"F={F} B={B} wave {WAVE}, {LEAVES} leaves, {N_A} rows")

    with open(os.path.join(ROOT, "tests", "data", "crosscheck_tree.json")) as f:
        golden = json.load(f)
    bins, g, h, _n, _F, Bt = make_case()
    for name, kw in (("full-scan", dict(ladder=())),
                     ("xla-gather", dict(ladder=LADDER)),
                     ("fused", dict(ladder=LADDER, fused=True))):
        sig = grow_single(bins, g, h, kernels="pallas", B=Bt, **kw)
        for k in ("n_nodes", "feat", "slot", "left", "right"):
            check(sig[k] == golden[k], f"stage C: toy {name} tree field {k} "
                  "differs from tests/data/crosscheck_tree.json")
        # the committed leaves are rounded to 6 decimals
        check(np.allclose(sig["leaf"], golden["leaf"], atol=2e-6, rtol=0),
              f"stage C: toy {name} leaves differ from the committed tree")
    print("stage C: the three Pallas programs reproduce "
          "tests/data/crosscheck_tree.json")
    return case, ref


def stage_d(dev_line: str, paths, X_test, case, ref_tree) -> None:
    import jax
    import numpy as np

    from ytklearn_tpu.gbdt.trainer import FUSED_MAX_ROWS

    devs = jax.devices()[:4]
    t0 = time.time()
    trainer, dd, res, scores_t, snap, model_path = cli_train(4, "d", *paths)
    check_training(res, snap, ROUNDS_A, "stage D")
    wave_summary(trainer, "stage D")
    check_model_scores(model_path, X_test, scores_t, "stage D")
    shards = dd.bins_t.addressable_shards
    n_cols = dd.bins_t.shape[1]
    on = sorted(s.device.id for s in shards)
    check(len(set(on)) == 4, f"stage D: bin-matrix shards on devices {on}")
    check(all(s.data.shape == (dd.bins_t.shape[0], n_cols // 4) for s in shards),
          f"stage D: shard shapes {[s.data.shape for s in shards]}, n={n_cols}")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
    check(min(in_use) > (dd.bins_t.nbytes // 4),
          f"stage D: bytes_in_use per device {in_use}")
    print(f"stage D [{dev_line}]: bin matrix {dd.bins_t.shape} in 4 shards of "
          f"{n_cols // 4} columns on devices {on}; bytes_in_use "
          f"{[b >> 20 for b in in_use]} MiB; wall {time.time() - t0:.1f}s")

    tree4, _wlog, secs = grow_full_width(
        case, full_width_spec(LADDER_C, FUSED_MAX_ROWS), devs)
    # The histograms are the same exact i32 sums, so every integer-valued
    # field must match bit for bit. leaf/hess/gain come out of f32 cumsums
    # over the 256 bins, which XLA orders differently for a shard's
    # (N, 7, 256) slice than for (N, 28, 256) (seen on four chips: only these
    # three fields differed): they must agree to that rounding. gain is a
    # difference of terms ~1e4x larger than itself, hence the absolute bound.
    diff = {k: float(np.abs(ref_tree[k].astype(np.float64) - tree4[k]).max())
            for k in ref_tree if not np.array_equal(ref_tree[k], tree4[k])}
    check(set(diff) <= {"leaf", "hess", "gain"},
          f"stage D: 4-device tree differs from the single-chip tree in {diff}")
    gmax = float(np.abs(ref_tree["gain"]).max())
    for k, rtol, atol in (("leaf", 1e-4, 1e-5), ("hess", 1e-4, 1e-5),
                          ("gain", 1e-2, 1e-4 * gmax)):
        check(np.allclose(ref_tree[k], tree4[k], rtol=rtol, atol=atol),
              f"stage D: 4-device {k} off by {diff.get(k)} (beyond f32 rounding)")
    print(f"stage D: 4-device mesh tree (Pallas per shard under shard_map, "
          f"psum_scatter + pargmax) == single-chip tree: splits, children, "
          f"depths, counts bit-identical; max |diff| of f32 fields {diff} "
          f"({secs:.1f}s)")


def main() -> int:
    t_start = time.time()
    sys.stdout.reconfigure(line_buffering=True)
    import jax

    devs = jax.devices()
    backend = jax.default_backend()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"jax {jax.__version__} backend={backend} "
          f"device_kind={devs[0].device_kind} count={len(devs)}", flush=True)
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (jax backend is {backend!r}); this "
              "smoke has no CPU mode", file=sys.stderr)
        return 2
    dev_line = f"{device['kind']} x{device['count']}"

    sys.path.insert(0, ROOT)
    from ytklearn_tpu import obs
    from ytklearn_tpu.compile_cache import configure_compile_cache

    # what runs is built from native/*.cpp as committed
    shutil.rmtree(os.path.join(ROOT, "native", "build"), ignore_errors=True)
    os.makedirs(OUT, exist_ok=True)
    print(f"compile cache: {configure_compile_cache()}")
    obs.configure(enabled=True)
    obs.health.install_trace_counters()

    paths = os.path.join(OUT, "smoke.train"), os.path.join(OUT, "smoke.test")
    seconds = {}

    def run(name, stage, *args):
        obs.reset()  # each stage reads its own counters and gauges
        t0 = time.time()
        out = stage(dev_line, *args)
        compiled = obs.snapshot()["counters"].get(
            "compile.traces.backend_compile_secs", 0.0)
        seconds[name] = (round(time.time() - t0, 1), round(compiled, 1))
        return out

    try:
        X_test = run("A", stage_a, paths)
        run("B", stage_b)
        case, ref_tree = run("C", stage_c)
        if len(devs) >= 4:
            run("D", stage_d, paths, X_test, case, ref_tree)
    finally:
        for p in paths:  # ~0.5 GB of text: not an artifact
            if os.path.exists(p):
                os.unlink(p)
    if len(devs) < 4:
        print(f"stage D: not run ({len(devs)} chip(s))")
    print(f"[{dev_line}] stage (wall s, backend compile s): {seconds}; total "
          f"{time.time() - t_start:.1f}s (bring-up observations, not metrics)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
