"""Per-component timing of the device GBDT engine at Higgs scale.

Times, with forced fetches (np.asarray on a slice) so async dispatch
can't fake the numbers:
  - hist_wave (Pallas) for wave sizes 16/32
  - _route_wave-equivalent position rewrite
  - one full grow() tree program
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ytklearn_tpu.gbdt.engine import GrowSpec, make_grow_tree
from ytklearn_tpu.gbdt.hist import hist_wave, pad_inputs


def force(x):
    return np.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[0]


def timeit(label, fn, reps=5):
    force(fn())  # compile + run to completion
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        force(out)  # per-rep sync: no dispatch pipelining in the timing
    dt = (time.perf_counter() - t0) / reps
    print(f"{label:40s} {dt*1e3:9.1f} ms", flush=True)
    return dt


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    F, B = 28, 256
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 255, size=(n, F)).astype(np.int32)
    bins_t_np, n_pad = pad_inputs(bins)
    del bins
    bins_t = jnp.asarray(bins_t_np)
    del bins_t_np
    g = jnp.asarray(rng.randn(n_pad).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(n_pad)).astype(np.float32))
    print(f"n={n} n_pad={n_pad}", flush=True)

    for NW in (16, 32):
        pos = jnp.asarray(rng.randint(0, 400, size=(n_pad,)).astype(np.int32))
        ids = jnp.asarray(np.arange(NW, dtype=np.int32))
        timeit(
            f"hist_wave N={NW} bf16",
            lambda: hist_wave(bins_t, pos, g, h, ids, B),
        )

    # route: NW sequential row-slice + rewrite passes
    from ytklearn_tpu.gbdt.engine import _route_wave

    NW = 16
    pos = jnp.asarray(rng.randint(0, 16, size=(n_pad,)).astype(np.int32))
    sel_valid = jnp.ones((NW,), bool)
    sel_nid = jnp.arange(NW, dtype=jnp.int32)
    sel_feat = jnp.asarray(rng.randint(0, F, NW).astype(np.int32))
    sel_slot = jnp.full((NW,), 128, jnp.int32)
    sel_lo = jnp.zeros((NW,), jnp.int32)
    sel_hi = jnp.full((NW,), B - 1, jnp.int32)
    sel_l = jnp.arange(16, 16 + NW, dtype=jnp.int32)
    sel_r = sel_l + 1

    route = jax.jit(
        lambda bt, p_: _route_wave(
            bt, p_, sel_valid, sel_nid, sel_feat, sel_slot, sel_lo, sel_hi,
            sel_l, sel_r, NW
        )
    )
    timeit("route wave of 16", lambda: route(bins_t, pos))

    # full tree
    spec = GrowSpec(
        F=F, B=B, max_nodes=509, wave=16, policy="loss", max_depth=60,
        max_leaves=255, lr=0.1, l1=0.0, l2=0.0, min_h=100.0, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0,
    )
    grow = jax.jit(make_grow_tree(spec))
    include = jnp.asarray(np.arange(n_pad) < n)
    fmask = jnp.ones((F,), bool)
    timeit(
        "grow full tree (255 leaves, wave 16)",
        lambda: grow(bins_t, include, g, h, fmask),
        reps=3,
    )


if __name__ == "__main__":
    main()
