"""Field-aware factorization machine.

Rebuild of reference optimizer/FFMHoagOptimizer.java:90 +
dataflow/FFMModelDataFlow.java (dim = n + n*F*k, V[feat, field, f]; x stores
(featIdx, val, fieldIdx) triples; field = feature-name prefix before
field_delim, mapped through model.field_dict_path).

TPU-first pairwise formulation: instead of the reference's O(width^2 * k)
per-row double loop, aggregate per *field pair*:
    T[a, b, :] = Σ_{p: field_p = a} val_p · V[feat_p, b, :]      (n, F, F, k)
    fx = x·w1 + 0.5 ( Σ_{a,b} T[a,b]·T[b,a]  -  Σ_p val_p² |V[feat_p, field_p]|² )

One lookup a slot: `prepare` builds one table from the flat vector, a row an
id: the id's F·k latent floats (field-major, as the flat layout has them)
and its first-order weight last, 1 + F·k floats; `scores_prepared` gathers
it once, under the scope `ffm.gather`, and autodiff makes one scatter-add of
it under the same scope. `scores(w, ...)` is the two composed (models/base.py).
The table is 165 MB at 2^18 ids and F·k = 156 and nothing of a row enters
it, so blocked evaluation (optimize/blocked.py) builds it once a pass,
outside the chunk scan, sums the chunks' gradients in the table's layout and
turns the sum back into the flat layout once (scope `blocked.prepare`).
The gathered array is (rows, width, 1 + F·k): the F·k floats lie on the
128-lane axis (156 -> 256, 1.6x), never k or F alone (k-minor pads 4 ->
128, 32x: 1.6 MB a row at F = 39).

The field-pair term, under the scope `ffm.pair`: T is built by one product
of the one-hot field matrix with the scaled rows, batched over rows, at
`Precision.HIGHEST` (a one-hot operand is exact in bfloat16, the float32
rows are not: without the stated precision the MXU rounds them to
bfloat16). T stays (rows, F, F·k); the (a, b) <-> (b, a) pairing is a
transpose of its reshape, which XLA lays out with rows on the lanes. The
diagonal is read off the scaled rows by a mask of each slot's own field.
Rows whose fields repeat, or differ from row to row, take the same path:
the one-hot is made from the `field` array of the chunk.

The gauges `ffm.stat.gather_width` (1 + F·k, or 1 without a latent part)
and `ffm.stat.fields` say what a model looks up. The flat layout the
optimizer, the dump and `apply_model_line` see is unchanged.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config.params import CommonParams
from ..io.fs import FileSystem
from ..io.reader import SparseDataset
from ..obs import gauge as obs_gauge
from ..obs.scopes import scope
from .base import ConvexModel, random_init


def load_field_dict(fs: FileSystem, path: str) -> Dict[str, int]:
    """field name -> index, file line order (reference:
    FFMModelDataFlow.java:234-241)."""
    fmap: Dict[str, int] = {}
    with fs.open(path) as f:
        for line in f:
            name = line.strip()
            if name and name not in fmap:
                fmap[name] = len(fmap)
    return fmap


class FFMModel(ConvexModel):
    name = "ffm"

    def __init__(self, params: CommonParams, n_features: int, n_fields: int):
        super().__init__(params, n_features)
        k = params.k
        if not (isinstance(k, (list, tuple)) and len(k) == 2):
            raise ValueError(f"ffm config k must be [first_order(0/1), latent_dim]: {k!r}")
        self.need_first_order = int(k[0]) >= 1
        self.sok = int(k[1])
        self.need_second_order = self.sok > 0
        self.n_fields = n_fields
        self.v_start = n_features
        # floats of the table row `scores` looks a slot up in, and the fields
        obs_gauge("ffm.stat.gather_width", 1 + n_fields * self.sok)
        obs_gauge("ffm.stat.fields", n_fields)

    @property
    def dim(self) -> int:
        return self.n_features * (1 + self.n_fields * self.sok)

    def regular_blocks(self):
        fo_start = 1 if self.params.model.need_bias else 0
        return [(fo_start, self.v_start), (self.v_start, self.dim)]

    def init_weights(self) -> np.ndarray:
        w = np.zeros((self.dim,), np.float32)
        w[self.v_start:] = random_init(self.params, self.dim - self.v_start)
        if self.params.model.need_bias:
            stride = self.n_fields * self.sok
            w[self.v_start : self.v_start + stride] = 0.0
        return w

    def _apply_mask(self, w):
        """Zero masked weight slices in-graph (see FMModel._apply_mask)."""
        if not self.need_first_order:
            fo_start = 1 if self.params.model.need_bias else 0
            w = w.at[fo_start : self.v_start].set(0.0)
        if not self.need_second_order:
            w = w.at[self.v_start :].set(0.0)
        elif self.params.model.need_bias and not self.params.bias_need_latent_factor:
            stride = self.n_fields * self.sok
            w = w.at[self.v_start : self.v_start + stride].set(0.0)
        return w

    def make_batch(self, ds: SparseDataset) -> Tuple[np.ndarray, ...]:
        if ds.field is None:
            raise ValueError("FFM requires a dataset ingested with a field map")
        return (ds.idx, ds.val, ds.field, ds.y, ds.weight)

    def prepare(self, w):
        """What a pass looks up in, made of the flat vector alone (once a
        pass under blocked evaluation): the (n_features, F·k + 1) table of
        the masked vector, an id's latent floats, field-major as the flat
        layout has them, and its first-order weight last. Without a latent
        part, the masked first-order weights."""
        nf = self.n_features
        w = self._apply_mask(w)
        if not self.need_second_order:
            return w[: self.v_start]
        V = w[self.v_start :].reshape(nf, self.n_fields * self.sok)
        return jnp.concatenate([V, w[:nf, None]], axis=1)

    def scores_prepared(self, table, *xargs):
        idx, val, field = xargs
        if not self.need_second_order:
            with scope("ffm.gather"):
                w1x = table[idx]
            return jnp.sum(val * w1x, axis=-1)
        F, k = self.n_fields, self.sok
        fk = F * k
        with scope("ffm.gather"):
            g = table[idx]  # (n, width, F·k + 1): the one lookup a slot
        wx = jnp.sum(val * g[..., fk], axis=-1)
        with scope("ffm.pair"):
            z = g[..., :fk] * val[..., None]  # x_p · V[feat_p, :, :]
            # T[a, (b, c)] = Σ_p [field_p = a] z[p, (b, c)]; the one-hot is
            # made inside the product's fusion, never stored
            onehot = (field[..., None, :] == jnp.arange(F)[:, None]).astype(z.dtype)
            T = jnp.einsum(
                "...aw,...wc->...ac", onehot, z, precision=lax.Precision.HIGHEST
            )
            T = T.reshape(T.shape[:-1] + (F, k))  # (n, a, b, c)
            cross = jnp.sum(T * jnp.swapaxes(T, -3, -2), axis=(-3, -2, -1))
            # p = q terms: x_p² |V[feat_p, field_p]|², read off z by a mask
            own = field[..., None] == jnp.arange(fk) // k
            diag = jnp.sum(jnp.where(own, z * z, 0.0), axis=(-2, -1))
        return wx + 0.5 * (cross - diag)

    def score_bytes_per_row(self, width: int) -> int:
        """What a row of a chunk holds at once under autodiff, padded as the
        chip tiles it (8 sublanes, 128 lanes): the scaled gathered rows
        (width, F·k + 1), the field-pair sums (F, F·k) and one cotangent of
        the larger, all with F·k on the lanes. At width 40, F 39, k 4: 120
        KiB (the v5e compiler's own count for a chunk's loss+gradient: 108
        KiB a row)."""
        F, fk = self.n_fields, self.n_fields * max(self.sok, 1)

        def pad(x, m):
            return -(-x // m) * m

        rows = pad(width, 8) * pad(fk + 1, 128)
        pairs = pad(F, 8) * pad(fk, 128)
        return (rows + pairs + max(rows, pairs)) * 4

    def suggest_row_chunk(self, n_rows: int, width: int, n_shards: int = 1):
        """`score_bytes_per_row` is the whole of a row here, backward
        included, so the budget is divided by it as it is (the base's x4
        stands for the cotangents it does not count)."""
        from ..optimize.blocked import suggest_chunk

        return suggest_chunk(n_rows, self.score_bytes_per_row(width), n_shards=n_shards)

    # -- model text I/O: name,w,v[field0 k..],v[field1 k..],... ----------

    def model_line(self, name, i, w, precision, is_bias):
        w = np.asarray(w)
        d = self.params.model.delim
        stride = self.n_fields * self.sok
        lat = w[self.v_start + i * stride : self.v_start + (i + 1) * stride]
        return f"{name}{d}{w[i]:f}{d}" + d.join(repr(float(v)) for v in lat)

    def apply_model_line(self, w, gidx, info: Sequence[str]):
        w[gidx] = float(info[1])
        stride = self.n_fields * self.sok
        start = self.v_start + gidx * stride
        for f in range(min(stride, len(info) - 2)):
            w[start + f] = float(info[2 + f])
