"""Micro-bench of the histogram kernel variants at Higgs shape on the
real chip. Times hist_wave-level calls directly so each variant compiles
in seconds (the whole-tree program costs ~5 min/compile).

Variants: feature-group width fg, block width bm, int8 vs bf16, u8 vs
i32 one-hot compares.
"""

from __future__ import annotations

import sys
import time
from functools import partial

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ytklearn_tpu.compile_cache import configure_compile_cache
    from ytklearn_tpu.gbdt.hist import _hist_pallas, _hist_pallas_q

    configure_compile_cache()

    n = 1280 * 8192  # 10.48M
    F, B, N = 28, 256, 32
    rng = np.random.RandomState(0)
    bins_host = rng.randint(0, 255, size=(F, n), dtype=np.uint8)
    bins_dev = jax.device_put(bins_host)
    pos = jax.device_put(rng.randint(0, 64, size=n).astype(np.int32))
    g = jax.device_put(rng.randn(n).astype(np.float32))
    h = jax.device_put(np.abs(rng.randn(n)).astype(np.float32))
    gq = jnp.clip(jnp.round(g * 50), -127, 127)
    hq = jnp.clip(jnp.round(h * 50), -127, 127)
    ids = jax.device_put(np.arange(N, dtype=np.int32))

    def timeit(name, fn, *args, reps=8):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.time() - t0) / reps * 1000
        print(f"{name:42s} {dt:8.2f} ms", flush=True)
        return dt

    # --- baselines at various fg / bm ------------------------------------
    for bm in (8192, 16384, 32768):
        bins4 = bins_dev.reshape(F, n // bm, 1, bm)
        for fg in (7, 14, 28):
            timeit(
                f"int8 bm={bm} fg={fg}",
                partial(_hist_pallas_q, B=B, bm=bm, fg=fg),
                bins4, pos, gq, hq, ids,
            )
    bins4 = bins_dev.reshape(F, n // 8192, 1, 8192)
    timeit(
        "bf16 bm=8192 fg=7",
        partial(_hist_pallas, B=B, bm=8192, fg=7, use_bf16=True),
        bins4, pos, g, h, ids,
    )

    # --- u8 one-hot compare variant (int8 dot) ---------------------------
    def hist_q_u8(bins4, pos, gq, hq, node_ids, B, bm, fg):
        F, nblk = bins4.shape[0], bins4.shape[1]
        N = node_ids.shape[0]
        nt = (((1,), (1,)), ((), ()))
        pos3 = pos.reshape(nblk, 1, bm)
        g3 = gq.reshape(nblk, 1, bm)
        h3 = hq.reshape(nblk, 1, bm)
        ids2 = node_ids.reshape(N, 1)

        def kernel(bins_ref, pos_ref, g_ref, h_ref, ids_ref, out_ref):
            blk = pl.program_id(1)
            p = pos_ref[0, 0, :][None, :]
            Pb = ids_ref[:, 0:1] == p
            P = Pb.astype(jnp.float32)
            gv = P * g_ref[0, 0, :][None, :]
            hv = P * h_ref[0, 0, :][None, :]
            PV = jnp.concatenate([gv, hv, P], axis=0).astype(jnp.int8)
            iota_b = jax.lax.broadcasted_iota(
                jnp.int32, (B, 1), 0
            ).astype(jnp.uint8)
            for fi in range(fg):
                b = bins_ref[fi, 0, 0, :][None, :]  # stays u8
                OH = (iota_b == b).astype(jnp.int8)
                acc = jax.lax.dot_general(
                    PV, OH, nt, preferred_element_type=jnp.int32
                )

                @pl.when(blk == 0)
                def _():
                    out_ref[fi, :, :] = acc

                @pl.when(blk > 0)
                def _():
                    out_ref[fi, :, :] = out_ref[fi, :, :] + acc

        return pl.pallas_call(
            kernel,
            grid=(F // fg, nblk),
            in_specs=[
                pl.BlockSpec((fg, 1, 1, bm), lambda fo, k: (fo, k, 0, 0)),
                pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
                pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
                pl.BlockSpec((1, 1, bm), lambda fo, k: (k, 0, 0)),
                pl.BlockSpec((N, 1), lambda fo, k: (0, 0)),
            ],
            out_specs=pl.BlockSpec((fg, 3 * N, B), lambda fo, k: (fo, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((F, 3 * N, B), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
        )(bins4, pos3, g3, h3, ids2)

    for bm in (8192, 32768):
        bins4 = bins_dev.reshape(F, n // bm, 1, bm)
        for fg in (7, 28):
            try:
                timeit(
                    f"int8 u8-OH bm={bm} fg={fg}",
                    partial(jax.jit, static_argnames=())(
                        partial(hist_q_u8, B=B, bm=bm, fg=fg)
                    ),
                    bins4, pos, gq, hq, ids,
                )
            except Exception as e:  # noqa: BLE001
                print(f"int8 u8-OH bm={bm} fg={fg} FAILED: {type(e).__name__}",
                      flush=True)

    # --- correctness spot check (u8 variant vs reference kernel) ---------
    bins4 = bins_dev.reshape(F, n // 8192, 1, 8192)
    a = _hist_pallas_q(bins4, pos, gq, hq, ids, B, 8192, 7)
    b = hist_q_u8(bins4, pos, gq, hq, ids, B=B, bm=8192, fg=7)
    print("u8 variant exact:", bool(jnp.all(a == b)), flush=True)


if __name__ == "__main__":
    main()
