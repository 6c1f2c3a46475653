"""Micro-benchmark the fused compact+gather+histogram kernel against the
XLA gather+hist formulation at several wave budgets R — the tuning tool
for gbdt/trainer.py's LADDER / FUSED_MAX_ROWS on real hardware.

K chained passes inside one program, one scalar fetched (dispatch cost
stays out of the number), like micro_hist_chain.py. Run on the chip:

    python scripts/micro_hist_gather.py [n_rows]

Off-TPU it runs the fused kernel through the Pallas interpreter (slow —
correctness smoke only; pass a small n).
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

from ytklearn_tpu.gbdt.hist import gather_table, hist_wave, hist_wave_gather

K = 10


@partial(jax.jit, static_argnames=("F", "R", "B", "N", "bm_g", "kernels"))
def chain_fused(table, pos, gq, hq, F: int, R: int, B: int, N: int, bm_g: int,
                kernels: str):
    """Compaction + fused gather/hist, K times; the compaction (mask,
    cumsum, index scatter, 1-D grad gathers) is included — it is part of
    every partitioned wave's real cost."""
    n = pos.shape[0]
    ids0 = jnp.arange(N, dtype=jnp.int32)
    iota_n = jnp.arange(n, dtype=jnp.int32)

    def body(i, carry):
        acc, ids = carry
        mask = jnp.zeros((n,), bool)
        for k in range(N):
            mask = mask | (pos == ids[k])
        csum = jnp.cumsum(mask.astype(jnp.int32))
        cnt = csum[-1]
        dest = jnp.where(mask, csum - 1, R)
        idx = jnp.zeros((R,), jnp.int32).at[dest].set(iota_n, mode="drop")
        valid = jnp.arange(R, dtype=jnp.int32) < cnt
        pg = jnp.where(valid, jnp.take(pos, idx), -1)
        gg = jnp.take(gq, idx)
        hg = jnp.take(hq, idx)
        out = hist_wave_gather(
            table, idx, pg, gg, hg, ids, F, B, precision="int8",
            kernels=kernels, bm_g=bm_g, interpret=kernels != "pallas",
        )
        s = out[0, 0, 0, 0].astype(jnp.float32)
        return acc + s, ids0 + (s * 0).astype(jnp.int32)

    acc, _ = jax.lax.fori_loop(0, K, body, (jnp.zeros(()), ids0))
    return acc


@partial(jax.jit, static_argnames=("R", "B", "N", "bm", "kernels"))
def chain_xla(rows, bins_t, pos, gq, hq, R: int, B: int, N: int, bm: int,
              kernels: str):
    """Compaction + XLA (R, F) row gather + transpose + full-scan kernel —
    the r5 partitioned path the fused kernel replaces."""
    n = pos.shape[0]
    F = rows.shape[1]
    ids0 = jnp.arange(N, dtype=jnp.int32)
    iota_n = jnp.arange(n, dtype=jnp.int32)

    def body(i, carry):
        acc, ids = carry
        mask = jnp.zeros((n,), bool)
        for k in range(N):
            mask = mask | (pos == ids[k])
        csum = jnp.cumsum(mask.astype(jnp.int32))
        cnt = csum[-1]
        dest = jnp.where(mask, csum - 1, R)
        idx = jnp.zeros((R,), jnp.int32).at[dest].set(iota_n, mode="drop")
        valid = jnp.arange(R, dtype=jnp.int32) < cnt
        pg = jnp.where(valid, jnp.take(pos, idx), -1)
        gg = jnp.take(gq, idx)
        hg = jnp.take(hq, idx)
        bt = jnp.transpose(jnp.take(rows, idx, axis=0)).astype(jnp.int32)
        if kernels == "pallas":
            bt = bt.reshape(F, R // bm, 1, bm)
        out = hist_wave(
            bt, pg, gg, hg, ids, B, bm=bm, precision="int8", kernels=kernels
        )
        s = out[0, 0, 0, 0].astype(jnp.float32)
        return acc + s, ids0 + (s * 0).astype(jnp.int32)

    acc, _ = jax.lax.fori_loop(0, K, body, (jnp.zeros(()), ids0))
    return acc


def timed(label, fn, *args, **kw):
    r = fn(*args, **kw)
    float(r)
    t0 = time.perf_counter()
    float(fn(*args, **kw))
    dt = (time.perf_counter() - t0) / K
    print(f"{label:52s} {dt*1e3:9.2f} ms/pass", flush=True)


def main():
    on_tpu = jax.default_backend() == "tpu"
    kernels = "pallas" if on_tpu else "dense"
    n = int(sys.argv[1]) if len(sys.argv) > 1 else (
        10_485_760 if on_tpu else 65_536
    )
    F, B, N = 28, 256, 64
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randint(0, 255, size=(n, F)).astype(np.uint8))
    bins_t = jnp.transpose(rows)
    pos = jnp.asarray(rng.randint(0, 509, size=(n,)).astype(np.int32))
    gq = jnp.asarray(rng.randint(-127, 128, n).astype(np.float32))
    hq = jnp.asarray(rng.randint(0, 128, n).astype(np.float32))
    print(f"n={n} F={F} B={B} wave N={N} backend={jax.default_backend()}",
          flush=True)

    bm = 16384 if on_tpu else 4096
    table = gather_table(bins_t)
    for div in (8, 32, 64, 128, 256, 512):
        want = -(-n // div)
        R_x = max(-(-want // bm) * bm, bm)
        R_f = max(-(-want // 1024) * 1024, 1024)
        if R_x >= n and R_f >= n:
            continue
        if R_x < n:
            timed(f"xla-gather  div={div:4d} R={R_x:9d}",
                  chain_xla, rows, bins_t, pos, gq, hq, R_x, B, N, bm, kernels)
        if R_f < n:
            timed(f"fused       div={div:4d} R={R_f:9d}",
                  chain_fused, table, pos, gq, hq, F, R_f, B, N, 1024,
                  kernels)


if __name__ == "__main__":
    main()
