"""Factorization machine.

Rebuild of reference optimizer/FMHoagOptimizer.java:88 (the O(nk)
sum/sum-of-squares trick) + dataflow/FMModelDataFlow.java (layout
[w1 (n_features)] ++ [V (n_features*k)], V random-init, bias latent zeroed;
model text `name,w,v1,...,vk`).

fx = x·w1 + 0.5 Σ_f [(Σ_j v_jf x_j)^2 - Σ_j (v_jf x_j)^2]; the gradient
falls out of autodiff identically to the reference's closed form. Gradient
masks (first/second order switches, bias latent) are applied by masking the
*weights inside the score*: masked slots start at 0 and their chain-rule
gradient is 0, which reproduces the reference's g[i]=0 zeroing exactly.

One lookup a slot: with a latent part (`k[1] > 0`) `prepare` builds one
(1 + k)-row table from the flat vector (row 0 the first-order weights, rows
1..k the latent rows) and `scores_prepared` gathers it once, under the scope
`fm.gather_v`; autodiff makes one scatter-add of it (and XLA a sort in
front), under the same scope. `scores(w, ...)` is the two composed
(models/base.py). Nothing of a row enters the table, so blocked evaluation
(optimize/blocked.py) builds it once a pass, outside the chunk scan, sums
the chunks' gradients in the table's layout and turns the sum back into the
flat layout once (scope `blocked.prepare`). Without a latent part
`prepare` is the masked first-order slice and its single gather runs under
`fm.gather_w`, which holds nothing otherwise. The gauge
`fm.stat.gather_width` (1 + k, or 1) says which path a model took. The flat
layout the optimizer, the dump and `apply_model_line` see is unchanged.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..config.params import CommonParams
from ..io.reader import SparseDataset
from ..obs import gauge as obs_gauge
from ..obs.scopes import scope
from .base import ConvexModel, random_init


class FMModel(ConvexModel):
    name = "fm"

    def __init__(self, params: CommonParams, n_features: int):
        super().__init__(params, n_features)
        k = params.k
        if not (isinstance(k, (list, tuple)) and len(k) == 2):
            raise ValueError(f"fm config k must be [first_order(0/1), latent_dim]: {k!r}")
        self.need_first_order = int(k[0]) >= 1
        self.sok = int(k[1])
        self.need_second_order = self.sok > 0
        self.v_start = n_features  # secondOrderIndexStart
        # rows of the table `scores` looks a slot up in: which path a run took
        obs_gauge("fm.stat.gather_width", 1 + self.sok)

    @property
    def dim(self) -> int:
        return self.n_features * (1 + self.sok)

    def regular_blocks(self):
        """Two blocks: first-order (bias excluded) and latent
        (reference: FMHoagOptimizer.getRegularStart/End)."""
        fo_start = 1 if self.params.model.need_bias else 0
        return [(fo_start, self.v_start), (self.v_start, self.dim)]

    def init_weights(self) -> np.ndarray:
        w = np.zeros((self.dim,), np.float32)
        w[self.v_start:] = random_init(self.params, self.dim - self.v_start)
        if self.params.model.need_bias:
            w[self.v_start : self.v_start + self.sok] = 0.0  # bias latent
        return w

    def _apply_mask(self, w):
        """Zero masked weight slices in-graph (static slice bounds, no big
        captured constants); masked slots init at 0 and get 0 gradient via
        the chain rule — reproducing the reference's g[i]=0 zeroing."""
        if not self.need_first_order:
            fo_start = 1 if self.params.model.need_bias else 0
            w = w.at[fo_start : self.v_start].set(0.0)
        if not self.need_second_order:
            w = w.at[self.v_start :].set(0.0)
        elif self.params.model.need_bias and not self.params.bias_need_latent_factor:
            w = w.at[self.v_start : self.v_start + self.sok].set(0.0)
        return w

    def prepare(self, w):
        """What a pass looks up in, made of the flat vector alone (once a
        pass under blocked evaluation): with a latent part the (1 + k,
        n_features) table of the masked vector, row 0 the first-order
        weights, rows 1..k the latent rows; without, the masked first-order
        weights."""
        w = self._apply_mask(w)
        if not self.need_second_order:
            return w[: self.v_start]
        # k-major: the (1+k, n, width) intermediate keeps width on the
        # 128-lane axis (pad e.g. 39->128, ~3.3x) instead of k (8->128,
        # 16x) — the k-minor layout is what OOM'd BENCH_r04
        # (f32[2M*39,8] lane-padded to 39.9 GB)
        Vt = w[self.v_start :].reshape(self.n_features, self.sok).T  # (k, nf)
        return jnp.concatenate([w[None, : self.v_start], Vt], axis=0)

    def scores_prepared(self, table, *xargs):
        idx, val = xargs
        if not self.need_second_order:
            with scope("fm.gather_w"):
                w1x = table[idx]
            return jnp.sum(val * w1x, axis=-1)
        # one table, one lookup a slot. On the chip a lookup costs per index
        # and nothing per byte (gather 9.9 ns, scatter-add 13.6 ns an index
        # at 8, 9 and 16 rows alike: PERF.md, PR 30), so a first-order
        # gather of its own cost 70% of the latent one. Autodiff makes one
        # scatter-add of the one gather, and prepare's transpose splits its
        # sum over the chunks back into the flat gradient.
        with scope("fm.gather_v"):
            g = table[:, idx]  # (1+k, n, width)
        wx = jnp.sum(val * g[0], axis=-1)
        vx = g[1:] * val[None]  # (k, n, width)
        S = jnp.sum(vx, axis=-1)  # Σ v x            (k, n)
        S2 = jnp.sum(vx * vx, axis=-1)  # Σ (v x)^2  (k, n)
        return wx + 0.5 * jnp.sum(S * S - S2, axis=0)

    def score_bytes_per_row(self, width: int) -> int:
        wp = -(-width // 128) * 128
        return max(self.sok, 1) * wp * 4

    # -- model text I/O: name,w,v1,...,vk --------------------------------

    def model_line(self, name, i, w, precision, is_bias):
        w = np.asarray(w)
        d = self.params.model.delim
        V = w[self.v_start :].reshape(self.n_features, self.sok)
        lat = d.join(repr(float(v)) for v in V[i])
        return f"{name}{d}{w[i]:f}{d}{lat}"

    def apply_model_line(self, w, gidx, info: Sequence[str]):
        w[gidx] = float(info[1])
        start = self.v_start + gidx * self.sok
        for f in range(self.sok):
            w[start + f] = float(info[2 + f])
