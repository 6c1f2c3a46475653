"""Shared scaffolding for the convex model family.

Each model supplies: flat-weight layout (+ init / grad masks), a pure-jnp
weighted-sum loss over its batch arrays, predictions, reg-range vectors, and
reference-compatible text model I/O. The optimizer (optimize/lbfgs.py) and
trainer (train.py) are model-agnostic.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..config.params import CommonParams
from ..io.fs import FileSystem
from ..io.reader import SparseDataset
from ..losses import create_loss


def random_init(params: CommonParams, size: int) -> np.ndarray:
    """Latent-factor init (reference: utils/RandomParamsUtils.java:37,
    param/RandomParams.java — normal(mean, std) or uniform[a, b))."""
    r = params.random
    rng = np.random.RandomState(r.seed)
    if r.mode == "uniform":
        return rng.uniform(
            r.uniform_range_start, r.uniform_range_end, size
        ).astype(np.float32)
    return (rng.randn(size) * r.normal_std + r.normal_mean).astype(np.float32)


class ConvexModel:
    """Base for L-BFGS-trained models."""

    name = "base"
    n_labels = 1  # K for multiclass families

    def __init__(self, params: CommonParams, n_features: int):
        self.params = params
        self.n_features = n_features
        self.loss = create_loss(params.loss.loss_function)

    # layout ------------------------------------------------------------
    @property
    def dim(self) -> int:
        raise NotImplementedError

    def init_weights(self) -> np.ndarray:
        return np.zeros((self.dim,), np.float32)

    def regular_blocks(self) -> List[Tuple[int, int]]:
        """[(start, end)] ranges regularized by l1[r]/l2[r]
        (reference: HoagOptimizer.getRegularStart/End overrides)."""
        raise NotImplementedError

    def reg_vectors(self, l1, l2) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-index reg coefficient vectors from the per-block l1/l2 lists
        (scalars broadcast to every block)."""
        blocks = self.regular_blocks()
        l1s = list(np.broadcast_to(np.atleast_1d(l1), (len(blocks),)))
        l2s = list(np.broadcast_to(np.atleast_1d(l2), (len(blocks),)))
        l1v = np.zeros((self.dim,), np.float32)
        l2v = np.zeros((self.dim,), np.float32)
        for (s, e), a, b in zip(blocks, l1s, l2s):
            l1v[s:e] = a
            l2v[s:e] = b
        return jnp.asarray(l1v), jnp.asarray(l2v)

    # batches ------------------------------------------------------------
    #: which make_batch elements are row-aligned (None = all); models with
    #: broadcast batch elements (e.g. the GBST gate mask) override this so
    #: blocked evaluation (optimize/blocked.py) chunks only row arrays
    batch_row_mask: Optional[Tuple[bool, ...]] = None

    def make_batch(self, ds: SparseDataset) -> Tuple[np.ndarray, ...]:
        """(idx, val, y, weight) padded-ELL by default; all arrays row-shard."""
        return (ds.idx, ds.val, ds.y, ds.weight)

    def score_bytes_per_row(self, width: int) -> int:
        """Approximate padded bytes of per-row score intermediates under the
        TPU (8,128) tiled layout — drives row-chunk selection. Subclasses
        with latent gathers (FM/FFM/GBST) override with their real cost."""
        return -(-width // 128) * 128 * 4

    def suggest_row_chunk(
        self, n_rows: int, width: int, n_shards: int = 1
    ) -> Optional[int]:
        """Row chunk for blocked loss/grad/score evaluation, or None when
        the whole batch fits the budget (the reference's blocked-CoreData
        contract, dataflow/CoreData.java:51-52; env overrides YTK_ROW_CHUNK
        / YTK_CHUNK_BUDGET_MB). `n_shards`: mesh shard count — the chunk
        decision is per-shard (each shard scans only its rows)."""
        from ..optimize.blocked import suggest_chunk

        # x4: forward intermediate + its backward cotangents/temps
        return suggest_chunk(
            n_rows, 4 * self.score_bytes_per_row(width), n_shards=n_shards
        )

    # kernels ------------------------------------------------------------
    #: A model MAY split its score in two: `prepare(w) -> p`, any pytree made
    #: of `w` alone (masks, reshapes, transposes, concats: work at the
    #: parameters' size that no row enters), and `scores_prepared(p, *xargs)`
    #: on it. `scores` is then their composition. Blocked evaluation
    #: (optimize/blocked.py) runs a declared `prepare` once a pass, outside
    #: its chunk scan, and turns the gradient back through it once; a model
    #: that leaves this None is evaluated `fn(w, chunk)` a chunk.
    prepare = None

    def scores_prepared(self, p, *xargs):
        raise NotImplementedError

    def scores(self, w, *xargs):
        if self.prepare is None:
            raise NotImplementedError
        return self.scores_prepared(self.prepare(w), *xargs)

    def _loss_of_scores(self, scores, y, weight):
        """Weighted-sum data loss; zero-weight padding rows masked via where
        (inf*0 from e.g. mape on padded labels must not NaN the sum)."""
        # loss() reduces multiclass trailing axes, so per_row is always (n,)
        per_row = jnp.where(weight > 0, self.loss.loss(scores, y), 0.0)
        return jnp.sum(weight * per_row)

    def pure_loss(self, w, *batch):
        *xargs, y, weight = batch
        return self._loss_of_scores(self.scores(w, *xargs), y, weight)

    def predicts(self, w, *batch):
        *xargs, _y, _w = batch
        return self.loss.predict(self.scores(w, *xargs))

    def pure_loss_prepared(self, p, *batch):
        *xargs, y, weight = batch
        return self._loss_of_scores(self.scores_prepared(p, *xargs), y, weight)

    def predicts_prepared(self, p, *batch):
        *xargs, _y, _w = batch
        return self.loss.predict(self.scores_prepared(p, *xargs))

    def _split(self, fn_p) -> Optional[Tuple]:
        return None if self.prepare is None else (self.prepare, fn_p)

    @property
    def loss_split(self) -> Optional[Tuple]:
        """`(prepare, pure_loss_prepared)` where the model declares the
        split, else None: what optimize/blocked.py's factories take beside
        `pure_loss`."""
        return self._split(self.pure_loss_prepared)

    @property
    def predicts_split(self) -> Optional[Tuple]:
        return self._split(self.predicts_prepared)

    # model I/O ----------------------------------------------------------
    def _part_paths(self, rank: int) -> Tuple[str, str]:
        p = self.params.model
        return (
            f"{p.data_path}/model-{rank:05d}",
            f"{p.data_path}_dict/dict-{rank:05d}",
        )

    def _feature_slice(self, rank: int, n_parts: int) -> Tuple[int, int]:
        avg = self.n_features // n_parts
        start = rank * avg
        end = self.n_features if rank == n_parts - 1 else (rank + 1) * avg
        return start, end

    def dump_model(
        self,
        fs: FileSystem,
        w: np.ndarray,
        precision: Optional[np.ndarray],
        feature_map: Dict[str, int],
        rank: int = 0,
        n_parts: int = 1,
    ) -> None:
        """Per-feature text lines; subclasses supply model_line(). Both
        files land via atomic write-then-replace so the serving registry's
        fingerprint watcher never parses a half-written dump. The model
        text is built first so the transform-stat sidecar can be stamped
        with its digest BEFORE the model lands (transform/sidecar.py —
        a crash between the writes is detected at serve load)."""
        p = self.params.model
        start, end = self._feature_slice(rank, n_parts)
        model_path, dict_path = self._part_paths(rank)
        model_lines: List[str] = []
        dict_lines: List[str] = []
        for name, i in feature_map.items():
            if not (start <= i < end):
                continue
            is_bias = name.lower() == p.bias_feature_name.lower()
            line = self.model_line(name, i, w, precision, is_bias)
            if line is None:
                continue
            model_lines.append(line + "\n")
            if not is_bias:
                dict_lines.append(name + "\n")
        self._stamp_transform_sidecar(fs, "".join(model_lines), rank, n_parts)
        with fs.atomic_open(model_path) as mf, fs.atomic_open(dict_path) as df:
            mf.writelines(model_lines)
            df.writelines(dict_lines)

    def _stamp_transform_sidecar(
        self, fs: FileSystem, model_text: str, rank: int, n_parts: int
    ) -> None:
        """Embed a digest of the model text about to land in the
        transform-stat sidecar (single-part rank0 dumps only — the
        production convex path; multi-part digests would need text from
        every rank, so those sidecars stay digestless and load like
        legacy ones)."""
        if rank != 0 or n_parts != 1:
            return
        if not self.params.feature.transform.switch_on:
            return
        from ..transform.sidecar import model_text_digest, stamp_sidecar_digest

        side = self.params.model.data_path + "_feature_transform_stat"
        stamp_sidecar_digest(fs, side, model_text_digest(model_text))

    def model_line(
        self, name: str, i: int, w: np.ndarray, precision, is_bias: bool
    ) -> Optional[str]:
        raise NotImplementedError

    def load_model(
        self, fs: FileSystem, feature_map: Dict[str, int]
    ) -> Optional[np.ndarray]:
        from ..io.fs import is_tmp_path

        p = self.params.model
        if not fs.exists(p.data_path):
            return None
        w = self.init_weights()
        for path in sorted(fs.recur_get_paths([p.data_path])):
            if is_tmp_path(path):
                continue  # in-flight atomic_open temp from a writer
            with fs.open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    info = line.split(p.delim)
                    if len(info) < 2:
                        continue
                    gidx = feature_map.get(info[0])
                    if gidx is not None:
                        self.apply_model_line(w, gidx, info)
        return w

    def apply_model_line(self, w: np.ndarray, gidx: int, info: Sequence[str]):
        raise NotImplementedError
