"""Gradient-boosted soft trees — gbmlr / gbsdt / gbhmlr / gbhsdt.

Rebuild of reference optimizer/GBMLRHoagOptimizer.java:130,
GBSDTHoagOptimizer.java:135, GBHMLRHoagOptimizer.java:136,
GBHSDTHoagOptimizer.java:142 + dataflow/GBMLRDataFlow.java (z-accumulation,
per-tree random init, instance/feature Bernoulli masks, tree-%05d model
text) + operation/GBMLROperation.java:39-124 (boosting outer loop).

One "tree" = a soft mixture: K experts gated by either a flat softmax
(gbmlr/gbsdt) or a complete-binary-tree of sigmoids (gbhmlr/gbhsdt, heap
layout — leaf prob is the product of gate probs along the root path).
Experts are per-feature linear functions (gbmlr/gbhmlr; stride 2K-1 per
feature = K-1 gates + K experts) or K global scalars (gbsdt/gbhsdt;
dim = K + n_features*(K-1)).

fx = z + Σ_p π_p(x)·expert_p(x)   (z = accumulated previous trees; RF: 0)
All four gradients fall out of autodiff; the reference's feature-mask
g[i]=0 zeroing is reproduced by multiplying gate weights with the mask
inside the score (chain rule zeroes the same slots).

Two evaluations of the same sum Σ_j val[r, j]·W[idx[r, j]], chosen from the
rows (`tree_output`). Rows handed over as `(idx, val)` and nothing else are
looked up: `W[idx]`, a `(rows, width, stride)` tensor, and by autodiff a
scatter-add of rows × width updates. Rows whose slots each hold ONE id in
every row (dense tabular rows: `io/reader.py::constant_slots`, observed once
at set-up by `boost.py` and handed to the model as `dense_ids`) need no
lookup a slot: the table's `width` rows are read once an evaluation and the
sum is the product `val @ W[dense_ids]` on the MXU at `Precision.HIGHEST`
(float32 means float32: at the default precision the MXU rounds both
operands to bfloat16); by autodiff the table's gradient is `val.T @
cotangent` and a scatter of `width` rows. `idx` is then not read at all.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config.params import CommonParams
from ..io.fs import FileSystem
from ..obs.scopes import scope
from .base import ConvexModel, random_init


def heap_leaf_probs(sig):
    """Leaf probabilities from (n, K-1) heap-ordered internal sigmoid gates
    (P(left child) = sigmoid; reference: GBHMLRHoagOptimizer mu/gx loop,
    same heap convention as loss/HSoftmaxFunction.java)."""
    K = sig.shape[-1] + 1
    level = jnp.ones(sig.shape[:-1] + (1,), sig.dtype)
    for _ in range(int(math.log2(K))):
        n = level.shape[-1]
        gates = jax.lax.dynamic_slice_in_dim(sig, n - 1, n, axis=-1)
        level = jnp.stack([level * gates, level * (1.0 - gates)], axis=-1).reshape(
            sig.shape[:-1] + (2 * n,)
        )
    return level


class GBSTModel(ConvexModel):
    """All four GBST variants; `variant` picks layout + gating.

    `dense_ids`: (width,) ids, the one id each slot holds in every row of
    the rows this instance evaluates, or None where some slot's id differs
    by row (a fact of the rows, observed by the caller; one instance a set
    of rows). It picks the evaluation inside `tree_output`, nothing else."""

    def __init__(
        self,
        params: CommonParams,
        n_features: int,
        variant: str,
        dense_ids: Optional[np.ndarray] = None,
    ):
        super().__init__(params, n_features)
        assert variant in ("gbmlr", "gbsdt", "gbhmlr", "gbhsdt")
        self.variant = variant
        self.K = int(params.k)
        self.hier = variant in ("gbhmlr", "gbhsdt")
        self.scalar_leaves = variant in ("gbsdt", "gbhsdt")
        if self.hier and (self.K & (self.K - 1)) != 0:
            raise ValueError(f"{variant} requires K a power of two, got {self.K}")
        self.is_rf = params.gbst_type == "random_forest"
        self.name = variant
        self.dense_ids = dense_ids

    # -- layout ----------------------------------------------------------

    @property
    def dim(self) -> int:
        K = self.K
        if self.scalar_leaves:
            return K + self.n_features * (K - 1)
        return self.n_features * (2 * K - 1)

    def regular_blocks(self):
        K = self.K
        bias = self.params.model.need_bias
        if self.scalar_leaves:
            # leaf block + gates (bias feature's gates excluded)
            # (reference: GBSDTHoagOptimizer.getRegularStart/End)
            return [(0, K), ((2 * K - 1) if bias else K, self.dim)]
        return [((2 * K - 1) if bias else 0, self.dim)]

    def init_weights(self, tree_seed: int = 0) -> np.ndarray:
        """Per-tree random re-init (reference: GBMLRDataFlow.initW /
        GBSDTDataFlow.initW — bias blocks zeroed; gbsdt leaves uniform in
        leaf_random_init_range)."""
        p = self.params
        K = self.K
        rng_params = p.random
        seed = rng_params.seed + tree_seed
        rng = np.random.RandomState(seed)

        def rand(size):
            if rng_params.mode == "uniform":
                return rng.uniform(
                    rng_params.uniform_range_start, rng_params.uniform_range_end, size
                ).astype(np.float32)
            return (rng.randn(size) * rng_params.normal_std + rng_params.normal_mean).astype(
                np.float32
            )

        w = rand(self.dim)
        if self.scalar_leaves:
            lo, hi = p.leaf_random_init_range
            w[:K] = rng.uniform(lo, hi, K).astype(np.float32)
            if p.model.need_bias:
                w[K : 2 * K - 1] = 0.0  # bias feature's gates
        else:
            if p.model.need_bias:
                w[: 2 * K - 1] = 0.0  # bias feature's whole block
        return w

    #: boost.py batch layout (idx, val, z, gate_mask, y, weight) — the gate
    #: mask is per-feature, not per-row
    batch_row_mask = (True, True, True, False, True, True)

    def score_bytes_per_row(self, width: int) -> int:
        """Dominant per-row intermediate of the lookup: the (width, 2K-1)
        weight gather (k-minor, pads 2K-1 -> 128). It sizes the row chunk
        whatever the rows are, so on dense rows (`dense_ids`), where no such
        tensor is made, it is an upper bound."""
        wp = -(-width // 8) * 8
        stride = 2 * self.K - 1 if not self.scalar_leaves else self.K - 1
        return wp * (-(-stride // 128) * 128) * 4

    # -- kernels ---------------------------------------------------------

    def tree_output(self, w, idx, val, gate_mask):
        """Current tree's output fx_tree(x) (no z). gate_mask is the
        per-feature Bernoulli mask (n_features,) f32 — multiplied into gate
        weights so masked features neither contribute nor get gradients.

        Two scopes, flat. Looked-up rows: `gbst.lookup` holds the per-slot
        gathers (and, through autodiff, their scatter-add with its sort),
        `gbst.mixture` the contractions, the gate probabilities and the
        weighted sum. Dense rows (`dense_ids`): `gbst.lookup` holds the
        one lookup of the masked table's `width` rows an evaluation,
        `gbst.mixture` the mask, the product and the rest; the 0/1 mask
        multiplies the table's rows where the lookup multiplies `val`,
        which is the same sum."""
        K = self.K
        c = self.dense_ids
        if self.scalar_leaves:
            U = w[K:].reshape(self.n_features, K - 1)
            if c is not None:
                with scope("gbst.mixture"):
                    Um = gate_mask[:, None] * U  # the mask on the table
                with scope("gbst.lookup"):
                    Uc = Um[c]  # (width, K-1)
                with scope("gbst.mixture"):
                    gate_in = jnp.dot(val, Uc, precision=lax.Precision.HIGHEST)
                    return self._gate_probs(gate_in) @ w[:K]
            with scope("gbst.lookup"):
                gm = gate_mask[idx]  # (n, width)
                Ur = U[idx]  # (n, width, K-1)
            with scope("gbst.mixture"):
                gate_in = jnp.einsum("nw,nwk->nk", val * gm, Ur)
                experts = w[:K]  # scalar leaves, broadcast
                pi = self._gate_probs(gate_in)
                return pi @ experts
        W = w.reshape(self.n_features, 2 * K - 1)
        if c is not None:
            with scope("gbst.mixture"):
                # the mask on the gates' columns of the table
                Wm = jnp.concatenate(
                    [gate_mask[:, None] * W[:, : K - 1], W[:, K - 1 :]], axis=1
                )
            with scope("gbst.lookup"):
                Wc = Wm[c]  # (width, 2K-1)
            with scope("gbst.mixture"):
                # one product for gates and experts
                out = jnp.dot(val, Wc, precision=lax.Precision.HIGHEST)  # (n, 2K-1)
                pi = self._gate_probs(out[:, : K - 1])
                return jnp.sum(pi * out[:, K - 1 :], axis=-1)
        with scope("gbst.lookup"):
            gm = gate_mask[idx]  # (n, width)
            Wr = W[idx]  # (n, width, 2K-1)
        with scope("gbst.mixture"):
            gate_in = jnp.einsum("nw,nwk->nk", val * gm, Wr[..., : K - 1])
            experts = jnp.einsum("nw,nwk->nk", val, Wr[..., K - 1 :])  # (n, K)
            pi = self._gate_probs(gate_in)
            return jnp.sum(pi * experts, axis=-1)

    def _gate_probs(self, gate_in):
        """(n, K-1) gate logits -> (n, K) mixture probabilities."""
        if self.hier:
            return heap_leaf_probs(jax.nn.sigmoid(gate_in))
        # softmax over [logits, 0] (reference appends implicit 0)
        z = jnp.concatenate([gate_in, jnp.zeros_like(gate_in[:, :1])], axis=1)
        return jax.nn.softmax(z, axis=-1)

    def scores(self, w, *xargs):
        idx, val, z, gate_mask = xargs
        fx = self.tree_output(w, idx, val, gate_mask)
        # GB: loss at z + tree; RF: tree alone (reference fx init)
        return fx if self.is_rf else z + fx

    def rf_predict_scores(self, w, idx, val, z, gate_mask, tree_num):
        """RF: averaged ensemble score (reference (z+fx)/treeNum)."""
        fx = self.tree_output(w, idx, val, gate_mask)
        return (z + fx) / tree_num

    # -- model text I/O (per tree) ---------------------------------------
    # reference: GBMLRDataFlow.dumpModel — tree-%05d/model-%05d with a
    # leading "k:K" line, per-feature `name,v0,...,v_{stride-1},` (trailing
    # delim), masked gate weights dumped as literal 0.0

    def dump_tree(
        self,
        fs: FileSystem,
        w: np.ndarray,
        gate_mask: np.ndarray,
        feature_map: Dict[str, int],
        tree_id: int,
        rank: int = 0,
    ) -> None:
        p = self.params.model
        K = self.K
        d = p.delim
        w = np.asarray(w)
        path = f"{p.data_path}/tree-{tree_id:05d}/model-{rank:05d}"
        dict_path = f"{p.data_path}_dict/dict-{rank:05d}"
        with fs.atomic_open(path) as mf, fs.atomic_open(dict_path) as df:
            mf.write(f"k:{K}\n")
            if self.scalar_leaves:
                # bare leaf-value line right after the header
                # (reference: GBSDTDataFlow.dumpModel leafsb)
                mf.write(d.join(repr(float(v)) for v in w[:K]) + "\n")
            for name, i in feature_map.items():
                is_bias = name.lower() == p.bias_feature_name.lower()
                if self.scalar_leaves:
                    vals = list(w[K + i * (K - 1) : K + (i + 1) * (K - 1)])
                    if not is_bias and gate_mask[i] == 0:
                        vals = [0.0] * (K - 1)
                else:
                    stride = 2 * K - 1
                    vals = list(w[i * stride : (i + 1) * stride])
                    if not is_bias and gate_mask[i] == 0:
                        vals[: K - 1] = [0.0] * (K - 1)
                mf.write(name + d + d.join(repr(float(v)) for v in vals) + d + "\n")
                if not is_bias:
                    df.write(name + "\n")

    def load_tree(
        self, fs: FileSystem, feature_map: Dict[str, int], tree_id: int
    ) -> Optional[np.ndarray]:
        p = self.params.model
        K = self.K
        tree_dir = f"{p.data_path}/tree-{tree_id:05d}"
        if not fs.exists(tree_dir):
            return None
        from ..io.fs import is_tmp_path

        w = np.zeros((self.dim,), np.float32)
        for path in sorted(fs.recur_get_paths([tree_dir])):
            if is_tmp_path(path):
                continue  # in-flight atomic_open temp from a writer
            with fs.open(path) as f:
                expect_leaves = False
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    if line.startswith("k:"):
                        expect_leaves = self.scalar_leaves
                        continue
                    info = [s for s in line.split(p.delim) if s != ""]
                    if expect_leaves:
                        # bare leaf line follows the k: header (GBSDT family)
                        w[:K] = [float(v) for v in info[:K]]
                        expect_leaves = False
                        continue
                    gidx = feature_map.get(info[0])
                    if gidx is None:
                        continue
                    if self.scalar_leaves:
                        start = K + gidx * (K - 1)
                        for j in range(K - 1):
                            w[start + j] = float(info[1 + j])
                    else:
                        stride = 2 * K - 1
                        for j in range(stride):
                            w[gidx * stride + j] = float(info[1 + j])
        return w

    def dump_tree_info(self, fs: FileSystem, finished: int, base_score: float) -> None:
        """reference: GBMLRDataFlow.dumpModelInfo."""
        p = self.params
        with fs.atomic_open(f"{p.model.data_path}/tree-info") as f:
            f.write(f"K:{self.K}\n")
            f.write(f"tree_num:{p.tree_num}\n")
            f.write(f"finished_tree_num:{finished}\n")
            f.write(f"uniform_base_prediction:{base_score}\n")

    def load_tree_info(self, fs: FileSystem) -> Optional[Dict[str, float]]:
        path = f"{self.params.model.data_path}/tree-info"
        if not fs.exists(path):
            return None
        out: Dict[str, float] = {}
        with fs.open(path) as f:
            for line in f:
                if ":" in line:
                    k, v = line.strip().split(":", 1)
                    out[k] = float(v)
        return out
