"""Cost-decomposition ablations for the device GBDT engine at Higgs scale.

Generates data ON DEVICE (no host->device copy), trains a few trees per
config, reports the steady trees/s from trainer.time_stats — and, since
r6, the engine's per-wave histogram log: every histogram pass records
[rows_scanned, rows_needed, splits, width], so the record SHOWS whether
late-tree waves cost O(wave rows) (partitioned budgets engaged) or O(n)
(full scans all the way down).

Usage: python scripts/ablate_engine.py [n_rows] [config ...]
  configs: b256 (default), b64 (4x fewer hist FLOPs), notest, wave32,
           part (the default program under the name the r6/r11 records
           use), goss / efb / goss+efb (device-side GOSS row sampling and
           exclusive feature bundling, alone and combined; `part` is the
           both-off baseline arm). Which partitioned passes run is chosen
           in code (gbdt/trainer.py LADDER); chip_smoke.py stage C compares
           the three row-selection strategies on the chip.

Since r11 the generated data carries an 8-column mutually-exclusive
sparse block next to the 28 dense features, so the efb arms exercise a
real bundle; every arm trains on the same data and records test AUC, and
when both a goss arm and the baseline ran, the run FAILS LOUD (exit 1,
after writing the record) if a GOSS arm's AUC falls more than
ABLATE_AUC_TOL (default 0.005) below the baseline arm's — the
quality-band assertion from the reference Higgs discipline applied to
the sampling ablation (one-sided: sampling reading high is not a
failure).

Env: ABLATE_TREES (default 10), ABLATE_RECORD=path to also write the
wave-log ablation artifact as JSON (e.g. ABLATION_r11.json),
ABLATE_BASELINE=path to a checked-in BENCH_*.json (any schema generation
— read_bench_record normalizes) to print a vs-baseline line per config,
ABLATE_AUC_TOL (default 0.005), ABLATE_GOSS=a,b (default 0.2,0.125).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

logging.basicConfig(level=logging.INFO, stream=sys.stdout)

_AB_VARS = ("YTK_GOSS_A", "YTK_GOSS_B", "YTK_EFB", "YTK_EFB_CONFLICT")


def _goss_env():
    a, _, b = os.environ.get("ABLATE_GOSS", "0.2,0.125").partition(",")
    return {"YTK_GOSS_A": a.strip(), "YTK_GOSS_B": b.strip() or "0.125"}


_ENV_OVERRIDES = {
    # config name -> env var settings applied for that run
    "goss": _goss_env,
    "efb": {"YTK_EFB": "1"},
    "goss+efb": lambda: dict(_goss_env(), YTK_EFB="1"),
}


def _apply_env(cfg: str):
    # every config starts from defaults: a previous config's A/B override
    # must never leak into (and mislabel) the next run's record. EFB is
    # pinned OFF for every arm that doesn't opt in (the lib default is
    # on), so b256/b64/part/goss/... keep their pre-r11 semantics on the
    # exclusive-block data and stay valid both-off baselines for the
    # check_bench_regress GOSS gate.
    for k in _AB_VARS:
        os.environ.pop(k, None)
    over = _ENV_OVERRIDES.get(cfg, {})
    if callable(over):
        over = over()
    env = dict({"YTK_EFB": "0"}, **over)
    for k, v in env.items():
        os.environ[k] = v


def _sentinel_hits(counters: dict) -> int:
    """Root health.* total for pre-v3 artifacts — the ONE definition
    lives in ytklearn_tpu.obs.health (bench.py writes with it; this
    fallback must recompute identically or the gate compares skew)."""
    from ytklearn_tpu.obs.health import total_sentinel_hits

    return total_sentinel_hits(counters)


def read_bench_record(path: str) -> dict:
    """Load a BENCH_*.json artifact, tolerating every schema generation:
    v1 (BENCH_r01..r05 — flat fields, no schema_version), v2+
    (schema_version + the obs counters/gauges block, v3 health_events),
    and the CI driver wrapper ({"cmd", "rc", "tail", "parsed": <line>} —
    the shape the checked-in BENCH_r*.json actually have). Returns a
    normalized dict; absent fields come back as None/empty."""
    with open(path) as f:
        rec = json.load(f)
    if "parsed" in rec and "cmd" in rec:  # CI driver wrapper
        rec = rec["parsed"] or {}
    obs_block = rec.get("obs") or {}
    counters = obs_block.get("counters") or {}
    return {
        "schema_version": int(rec.get("schema_version", 1)),
        "metric": rec.get("metric"),
        "trees_per_sec": rec.get("value"),
        "auc": rec.get("auc"),
        "logloss": rec.get("logloss"),
        "trees": rec.get("trees"),
        "mxu_pct_peak": rec.get("mxu_pct_peak"),
        "hbm_pct_peak": rec.get("hbm_pct_peak"),
        "downgrades": rec.get(
            "downgrades", int(counters.get("gbdt.downgrade.total", 0))
        ),
        "health_events": int(rec.get("health_events", _sentinel_hits(counters))),
        "obs": obs_block,
        "raw": rec,
    }


def wave_table(wave_log: np.ndarray, tree: int = -1):
    """[(rows_scanned, rows_needed, splits, width, rows_sampled)] for one
    tree — the O(wave rows) / O(sampled wave rows) evidence table."""
    wl = wave_log[tree]
    used = wl[:, 3] > 0
    return [[int(v) for v in row] for row in wl[used].tolist()]


def main() -> None:
    import jax
    import jax.numpy as jnp

    from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams, ModelParams
    from ytklearn_tpu.gbdt.data import GBDTData
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    configs = sys.argv[2:] or ["b256"]
    n_trees = int(os.environ.get("ABLATE_TREES", 10))
    record_path = os.environ.get("ABLATE_RECORD")
    auc_tol = float(os.environ.get("ABLATE_AUC_TOL", "0.005"))
    baseline = None
    if os.environ.get("ABLATE_BASELINE"):
        baseline = read_bench_record(os.environ["ABLATE_BASELINE"])
        print(
            f"baseline {os.environ['ABLATE_BASELINE']} "
            f"(schema v{baseline['schema_version']}): "
            f"{baseline['trees_per_sec']} trees/s",
            flush=True,
        )
    F_dense, F_excl = 28, 8
    F = F_dense + F_excl
    n_test = max(n // 10, 1024)
    n_all = n + n_test

    key = jax.random.PRNGKey(0)
    kx, ke, kg, kv = jax.random.split(key, 4)
    X = jax.random.normal(kx, (n_all, F_dense), jnp.float32)
    # mutually-exclusive sparse block (one-of-8 nonneg per row) so the efb
    # arms bundle something real; the block carries signal so bundled
    # splits matter
    grp = jax.random.randint(kg, (n_all,), 0, F_excl)
    vals = jax.random.uniform(kv, (n_all,), jnp.float32) + 0.25
    Xs = jnp.zeros((n_all, F_excl), jnp.float32).at[
        jnp.arange(n_all), grp
    ].set(vals)
    X = jnp.concatenate([X, Xs], axis=1)
    logit = (
        1.5 * X[:, 0] * X[:, 1]
        + jnp.sin(X[:, 2] * 2)
        + 0.8 * (X[:, 3] > 0.5)
        - 0.5 * X[:, 4] ** 2
        + 1.2 * X[:, F_dense] - 0.9 * X[:, F_dense + 3]
    )
    y = (logit + jax.random.normal(ke, (n_all,)) * 0.5 > 0).astype(jnp.float32)
    y.block_until_ready()
    names = [f"f{i}" for i in range(F)]

    def mk(lo, hi):
        return GBDTData(
            X=X[lo:hi], y=y[lo:hi], weight=np.ones(hi - lo, np.float32),
            n_real=hi - lo, feature_names=names,
        )

    train, test = mk(0, n), mk(n, n_all)

    record = {"n_rows": n, "configs": {}}
    for cfg in configs:
        _apply_env(cfg)
        max_cnt = 63 if cfg == "b64" else 255
        wave = {"wave32": 32, "wave42": 42, "wave64": 64}.get(cfg, 16)
        params = GBDTParams(
            round_num=n_trees,
            max_depth=60,
            max_leaf_cnt=255,
            tree_grow_policy="loss",
            learning_rate=0.1,
            min_child_hessian_sum=100.0,
            loss_function="sigmoid",
            eval_metric=["auc"],
            approximate=[ApproximateSpec(type="sample_by_quantile", max_cnt=max_cnt)],
            model=ModelParams(data_path="/tmp/ablate_model", dump_freq=0),
        )
        t0 = time.time()
        tr = GBDTTrainer(params, engine="device", wave=wave)
        res = tr.train(train=train, test=test)
        stats = {k: round(v, 1) for k, v in tr.time_stats.items()
                 if isinstance(v, float)}
        steady = tr.time_stats.get("trees_per_sec_steady", 0)
        auc = float(res.test_metrics.get("auc", float("nan")))
        print(
            f"CONFIG {cfg}: steady={steady:.3f} trees/s auc={auc:.4f} "
            f"stats={stats}",
            flush=True,
        )
        if baseline and baseline.get("trees_per_sec"):
            print(
                f"CONFIG {cfg}: vs baseline "
                f"{steady / baseline['trees_per_sec']:.2f}x",
                flush=True,
            )
        entry = {
            "steady_trees_per_sec": tr.time_stats.get("trees_per_sec_steady", 0.0),
            "auc": auc,
            "test_loss": (
                float(res.test_loss) if res.test_loss is not None else None
            ),
            "time_stats": {
                k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in tr.time_stats.items()
            },
        }
        if tr._efb_plan is not None:
            entry["efb_plan"] = tr._efb_plan.summary()
        if getattr(tr, "wave_log", None) is not None:
            # last tree: the representative late-boosting shape; the first
            # tree shows the identical pattern one round earlier
            entry["last_tree_waves"] = wave_table(tr.wave_log, tree=-1)
            entry["wave_columns"] = [
                "rows_scanned", "rows_needed", "splits", "hist_width",
                "rows_sampled",
            ]
            wl = tr.wave_log
            used = wl[..., 3] > 0
            entry["hist_rows_scanned_total"] = float((wl[..., 0] * used).sum())
            entry["hist_rows_needed_total"] = float((wl[..., 1] * used).sum())
            # scan/need ratio: 1.0 = perfectly leaf-partitioned histogram
            # cost; n/need >> 1 on a full-scan config's late waves
            need = max(entry["hist_rows_needed_total"], 1.0)
            entry["scan_over_need"] = round(
                entry["hist_rows_scanned_total"] / need, 2
            )
        record["configs"][cfg] = entry

    # GOSS quality-band assertion: sampling must not buy its speed with
    # AUC — every goss arm must stay within auc_tol BELOW the both-off
    # baseline arm (one-sided: at short runs GOSS's amplification often
    # reads slightly HIGH, which is not a quality failure). Fails loud
    # AFTER the record is written (never destroy the artifact).
    band_fails = []
    base_arm = next(
        (c for c in ("part", "b256") if c in record["configs"]), None
    )
    if base_arm is not None:
        base_auc = record["configs"][base_arm]["auc"]
        for cfg in record["configs"]:
            if not cfg.startswith("goss"):
                continue
            auc = record["configs"][cfg]["auc"]
            if not (auc >= base_auc - auc_tol):  # NaN-safe: NaN fails
                band_fails.append(
                    f"{cfg} AUC {auc:.4f} fell below {base_arm} "
                    f"{base_auc:.4f} - tol {auc_tol}"
                )

    if record_path:
        with open(record_path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"ablation record written: {record_path}", flush=True)

    for msg in band_fails:
        print(f"QUALITY BAND FAIL: {msg}", file=sys.stderr, flush=True)
    if band_fails:
        sys.exit(1)


if __name__ == "__main__":
    main()
