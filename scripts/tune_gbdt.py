"""Sweep device-engine knobs (wave width, hist precision) on the real
chip at the Higgs acceptance shape. One process: data + binning once,
then one short training run per config; prints steady-state trees/s.

Usage: python scripts/tune_gbdt.py [n_trees] [rows]
"""

from __future__ import annotations

import sys
import time

import numpy as np


def main() -> None:
    import jax

    import jax.numpy as jnp

    from ytklearn_tpu.compile_cache import configure_compile_cache
    from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams, ModelParams
    from ytklearn_tpu.gbdt.data import GBDTData
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    configure_compile_cache()
    n_trees = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 10_500_000
    F = 28

    key = jax.random.PRNGKey(0)
    kx, ke = jax.random.split(key)
    X = jax.random.normal(kx, (n, F), jnp.float32)
    logit = (
        1.5 * X[:, 0] * X[:, 1]
        + jnp.sin(X[:, 2] * 2)
        + 0.8 * (X[:, 3] > 0.5)
        - 0.5 * X[:, 4] ** 2
        + 0.3 * X[:, 5] * X[:, 6]
    )
    y = (logit + jax.random.normal(ke, (n,)) * 0.5 > 0).astype(jnp.float32)
    y.block_until_ready()
    train = GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[f"f{i}" for i in range(F)],
    )

    configs = [
        (32, "int8"),
        (42, "int8"),
        (48, "int8"),
        (64, "int8"),
        (96, "int8"),
        (32, "bf16"),
        (42, "bf16"),
    ]
    results = []
    for wave, prec in configs:
        params = GBDTParams(
            round_num=n_trees,
            max_depth=60,
            max_leaf_cnt=255,
            tree_grow_policy="loss",
            learning_rate=0.1,
            min_child_hessian_sum=100.0,
            loss_function="sigmoid",
            eval_metric=[],
            approximate=[ApproximateSpec(type="sample_by_quantile", max_cnt=255)],
            model=ModelParams(data_path="/tmp/tune_gbdt_model", dump_freq=0),
        )
        t0 = time.time()
        tr = GBDTTrainer(params, engine="device", hist_precision=prec, wave=wave)
        res = tr.train(train=train)
        tps = tr.time_stats.get("trees_per_sec_steady", float("nan"))
        print(
            f"RESULT wave={wave} prec={prec} trees/s={tps:.3f} "
            f"loss={res.train_loss:.4f} wall={time.time()-t0:.0f}s",
            flush=True,
        )
        if np.isfinite(tps):
            results.append((tps, wave, prec))
        else:
            print(f"SKIP wave={wave} prec={prec}: no steady-state window "
                  "(need >1 sync round)", flush=True)
    results.sort(reverse=True)
    print("BEST:", results[:3])


if __name__ == "__main__":
    main()
