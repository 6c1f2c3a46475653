"""Pallas <-> sharded cross-check artifact.

Pins the two seams of the multi-chip claim with ONE recorded equivalence
(r4 VERDICT weak #3): the single-chip TPU Pallas growth program and the
8-shard dense growth program (shard_map + psum_scatter + pargmax — the
same program structure that runs per-shard on a real multi-chip mesh)
must grow the IDENTICAL tree on identical data. int8 histogram mode makes
the equality exact: histogram sums are order-independent i32.

Run on a machine with a TPU chip:

    python scripts/cross_check.py

It grows the tree four ways — TPU Pallas full-scan, TPU Pallas
leaf-partitioned (XLA gather), TPU Pallas FUSED-partitioned (the r6
default: compact+gather+histogram in one kernel), CPU 8-device sharded
dense — asserts equality, and records the tree to
tests/data/crosscheck_tree.json. The committed golden file lets the CPU
test suite (tests/test_crosscheck.py) re-derive the sharded tree AND the
fused-partitioned tree (Pallas interpreter) and compare against what the
TPU Pallas path produced, without TPU hardware in the loop.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def make_case():
    """Deterministic case with exact binning (few distinct values) and
    precomputed f32 grads, so every backend sees bit-identical inputs."""
    rng = np.random.RandomState(42)
    n, F, B = 32768, 8, 64
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    # plant signal so splits are meaningful
    logit = (
        0.08 * bins[:, 0]
        - 0.05 * bins[:, 1]
        + 0.3 * ((bins[:, 2] > 32) & (bins[:, 3] < 16))
    )
    y = (logit + rng.randn(n) > 1.0).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(logit - 1.0))).astype(np.float32)
    g = (p - y).astype(np.float32)
    h = np.maximum(p * (1 - p), 1e-6).astype(np.float32)
    return bins, g, h, n, F, B


LADDER = (8, 32)  # the partitioned runs' budget divisors


def spec_for(F, B, kernels, ladder, fused=False, fused_interpret=False):
    from ytklearn_tpu.gbdt.engine import GrowSpec
    from ytklearn_tpu.gbdt.trainer import FUSED_MAX_ROWS

    return GrowSpec(
        F=F, B=B, max_nodes=31, wave=4, policy="loss", max_depth=20,
        max_leaves=16, lr=0.1, l1=0.0, l2=1.0, min_h=1.0, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0, precision="int8",
        kernels=kernels, ladder=ladder,
        bm=4096,  # small blocks so the 32k-row case tiles on the TPU path
        bm_g=1024,
        # unfused rungs take the XLA gather
        fused_max_rows=FUSED_MAX_ROWS if fused or fused_interpret else 0,
        fused_interpret=fused_interpret,
    )


def tree_sig(tr) -> dict:
    return {
        "feat": np.asarray(tr.feat).tolist(),
        "slot": np.asarray(tr.slot).tolist(),
        "left": np.asarray(tr.left).tolist(),
        "right": np.asarray(tr.right).tolist(),
        "leaf": [round(float(v), 6) for v in np.asarray(tr.leaf)],
        "n_nodes": int(tr.n_nodes),
    }


def grow_single(
    bins, g, h, kernels, ladder, devices=None, B=None, fused=False,
    fused_interpret=False,
):
    import jax
    import jax.numpy as jnp

    from ytklearn_tpu.gbdt.engine import make_grow_tree

    n, F = bins.shape
    B = int(bins.max()) + 1 if B is None else B
    mesh = None
    if devices is not None:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(devices), ("data",))
    spec = spec_for(F, B, kernels, ladder, fused, fused_interpret)
    grow = make_grow_tree(spec, mesh=mesh)
    bins_t = np.ascontiguousarray(bins.T)
    args = (
        jnp.asarray(bins_t),
        jnp.ones((n,), bool),
        jnp.asarray(g),
        jnp.asarray(h),
        jnp.ones((F,), bool),
    )
    if mesh is not None and mesh.devices.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        args = (
            jax.device_put(args[0], NamedSharding(mesh, P(None, "data"))),
            jax.device_put(args[1], NamedSharding(mesh, P("data"))),
            jax.device_put(args[2], NamedSharding(mesh, P("data"))),
            jax.device_put(args[3], NamedSharding(mesh, P("data"))),
            jax.device_put(args[4], NamedSharding(mesh, P("data"))),
        )
    tr, pos, _, _wlog = jax.jit(lambda *a: grow(*a))(*args)
    return tree_sig(tr)


def main():
    import jax

    bins, g, h, n, F, B = make_case()
    golden_path = os.path.join(
        os.path.dirname(__file__), "..", "tests", "data", "crosscheck_tree.json"
    )

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"default backend is {backend}, need the TPU chip", file=sys.stderr)
        return 2

    sig_pallas = grow_single(bins, g, h, kernels="pallas", ladder=(), B=B)
    sig_pallas_part = grow_single(bins, g, h, kernels="pallas", ladder=LADDER, B=B)
    # the r6 default TPU path: partitioned budgets through the FUSED
    # compact+gather+histogram kernel
    sig_pallas_fused = grow_single(
        bins, g, h, kernels="pallas", ladder=LADDER, fused=True, B=B
    )

    # CPU 8-device sharded dense in-process (cpu backend coexists with tpu)
    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        print("need 8 CPU devices: run with JAX_NUM_CPU_DEVICES=8 or "
              "--xla_force_host_platform_device_count=8", file=sys.stderr)
        return 2
    sig_sharded = grow_single(
        bins, g, h, kernels="dense", ladder=(), devices=cpus[:8], B=B
    )

    ok = sig_pallas == sig_pallas_part == sig_pallas_fused == sig_sharded
    os.makedirs(os.path.dirname(golden_path), exist_ok=True)
    if ok:
        with open(golden_path, "w") as f:
            json.dump(sig_pallas, f, indent=0)
        print(f"golden tree recorded: {golden_path}")
    out = {
        "ok": ok,
        "n_nodes": sig_pallas["n_nodes"],
        "pallas_eq_partitioned": sig_pallas == sig_pallas_part,
        "pallas_eq_fused_partitioned": sig_pallas == sig_pallas_fused,
        "pallas_eq_sharded_dense": sig_pallas == sig_sharded,
    }
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
