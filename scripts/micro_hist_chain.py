"""True hist-kernel cost: K chained passes inside ONE program, one scalar
fetched — per-dispatch and device->host sync costs stay out of the number.

The chain feeds a zero derived from each output into the next pass's ids
so XLA cannot hoist the loop body.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ytklearn_tpu.gbdt.hist import _hist_pallas, pad_inputs

K = 10


@partial(jax.jit, static_argnames=("N", "B", "bm", "fg", "bf16"))
def chain(bins_t, pos, g, h, N: int, B: int, bm: int, fg: int, bf16: bool):
    ids0 = jnp.arange(N, dtype=jnp.int32)

    def body(i, carry):
        acc, ids = carry
        out = _hist_pallas(bins_t, pos, g, h, ids, B, bm, fg, bf16)
        s = out[0, 0, 0]
        return acc + s, ids0 + (s * 0).astype(jnp.int32)

    acc, _ = jax.lax.fori_loop(0, K, body, (jnp.zeros(()), ids0))
    return acc


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    F, B = 28, 256
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 255, size=(n, F)).astype(np.int32)
    bins_t_np, n_pad = pad_inputs(bins, bm=32768)
    del bins
    bins_t = jnp.asarray(bins_t_np)
    del bins_t_np
    g = jnp.asarray(rng.randn(n_pad).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(n_pad)).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, 509, size=(n_pad,)).astype(np.int32))
    print(f"n={n} n_pad={n_pad}", flush=True)

    for N in (16, 32):
        for bm in (8192, 16384, 32768):
            for fg in (7, 14, 28):
                try:
                    r = chain(bins_t, pos, g, h, N, B, bm, fg, True)
                    float(r)
                    t0 = time.perf_counter()
                    float(chain(bins_t, pos, g, h, N, B, bm, fg, True))
                    dt = (time.perf_counter() - t0) / K
                    print(f"N={N:3d} bm={bm:6d} fg={fg:2d}: {dt*1e3:7.1f} ms/pass", flush=True)
                except Exception as e:
                    print(f"N={N:3d} bm={bm:6d} fg={fg:2d}: FAIL {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
