"""Per-N, per-H times of the full-scan histogram kernel `gbdt_hist_scan`
(`gbdt/hist.py::_hist_pallas`) on the chip, at the two GBDT cells' shapes:
Higgs (28 columns, 10,502,144 rows, one-byte tiles) and Epsilon (2,000
columns, 409,600 rows, packed words), B = 256, bf16. H = 1 is the whole bin
one-hot; H > 1 factors it (`hist.onehot_split` picks H from N and B). Each
factored pass is checked against H = 1: counts equal, sums close.

    python scripts/tune_hist_kernel.py [out.json]

Prints one line a (shape, N, H), and writes the table as JSON to out.json
when it is given. Exits non-zero off the chip: a CPU timing is not a
device number.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {  # name: (columns, row blocks of 16,384, packed words)
    "higgs": (28, 641, False),
    "epsilon": (2000, 25, True),
}
# the H tried at each N: the rule's neighbours, and 1
SPLITS = {1: (1, 2, 4, 8, 16), 2: (1, 2, 4, 8, 16), 4: (1, 2, 4, 8),
          8: (1, 2, 4, 8), 16: (1, 2, 4), 32: (1, 2), 64: (1, 2)}
B, REPS = 256, 5


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ytklearn_tpu.compile_cache import configure_compile_cache
    from ytklearn_tpu.gbdt import hist

    if jax.default_backend() != "tpu":
        print(f"no TPU (backend {jax.default_backend()!r})", file=sys.stderr)
        return 2
    configure_compile_cache()
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    bm = hist.BM_DEFAULT
    rows = []
    for shape, (F, nblk, packed) in SHAPES.items():
        n = nblk * bm
        k = jax.random.split(jax.random.PRNGKey(0), 5)
        bins_t = jax.random.randint(k[0], (F, n), 0, B).astype(jnp.uint8)
        tiles = jax.block_until_ready(hist.tile_bins(bins_t, bm, pack=packed))
        del bins_t
        g = jax.random.normal(k[1], (n,), jnp.float32)
        h = jax.random.uniform(k[2], (n,), jnp.float32)
        fg = hist._pick_fg(F)
        for N, splits in SPLITS.items():
            pos = jax.random.randint(k[3], (n,), 0, 2 * N)
            ids = jnp.arange(N, dtype=jnp.int32)
            base = None
            for H in splits:
                run = partial(hist._hist_pallas, tiles, pos, g, h, ids, B, bm,
                              fg, True, H)
                t0 = time.time()
                try:
                    got = jax.block_until_ready(run())
                except Exception as e:  # a refused H is a row, not the end
                    print(json.dumps(dict(shape=shape, N=N, H=H,
                                          error=str(e).splitlines()[0])))
                    continue
                compile_s = time.time() - t0
                t0 = time.time()
                for _ in range(REPS):
                    out = run()
                jax.block_until_ready(out)
                ms = (time.time() - t0) / REPS * 1e3
                got = np.asarray(got).reshape(F, 3, N, B)
                if base is None:
                    base, cnt_ok, gap = got, True, 0.0
                else:
                    cnt_ok = bool(np.array_equal(got[:, 2], base[:, 2]))
                    scale = np.abs(base[:, :2]).max() + 1e-30
                    gap = float(np.abs(got[:, :2] - base[:, :2]).max() / scale)
                row = dict(shape=shape, F=F, n=n, N=N, H=H, ms=round(ms, 3),
                           rule=hist.onehot_split(N, B), counts_equal=cnt_ok,
                           sum_gap=gap, compile_s=round(compile_s, 1))
                rows.append(row)
                print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(dict(device=jax.devices()[0].device_kind, rows=rows), f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
