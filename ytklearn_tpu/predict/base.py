"""Online/offline predictor side-stack — no mesh, no collectives.

Rebuild of reference predictor/OnlinePredictor.java (abstract API :120-182,
ResultSaveMode/PredictType enums :51-90, batchPredictFromFiles :174) as a
standalone host library: a trained model's text files + the training config
are enough to serve `score/predict/loss` on feature dicts.

The TPU stays out of the hot path by design (the reference predictor is
likewise mp4j-free): per-sample scoring is numpy; only the activation
(loss.predict) may touch jax.
"""

from __future__ import annotations

import logging
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from ..config import hocon
from ..eval import EvalSet
from ..io.fs import FileSystem, LocalFileSystem, create_filesystem
from ..io.reader import load_transform_hook
from ..obs import heartbeat as obs_heartbeat, inc as obs_inc, span as obs_span

log = logging.getLogger("ytklearn_tpu.predict")

SAVE_MODES = ("predict_result_only", "label_and_predict", "predict_as_feature")

#: losses whose predict() is the identity (LossFunction.predict default or
#: the multiclass-margin identity override) — the activation fast path
#: below must list them explicitly, because a wrong identity assumption
#: would silently serve raw scores for e.g. sigmoid
_IDENTITY_ACTIVATIONS = {
    "l2", "l1", "huber", "mape", "inv_mape", "smape",
    "hinge", "l2_hinge", "smooth_hinge", "exponential",
    "multiclass_hinge", "multiclass_l2_hinge", "multiclass_smooth_hinge",
    "base",
}


def _np_sigmoid(s):
    s = np.asarray(s, np.float64)
    t = np.exp(-np.abs(s))  # stable: never exponentiates a large positive
    return np.where(s >= 0.0, 1.0 / (1.0 + t), t / (1.0 + t))


def _np_softmax(s):
    s = np.asarray(s, np.float64)
    z = s - np.max(s, axis=-1, keepdims=True)
    e = np.exp(z)
    return e / np.sum(e, axis=-1, keepdims=True)


def numpy_activation(loss):
    """Host-numpy mirror of `loss.predict`, or None when only the jnp
    implementation exists (hsoftmax's heap walk).

    The per-sample serving hot path must not dispatch jnp per request: a
    single `loss.predict(score)` call is a dispatch plus a device->host
    sync per request — the same lesson batch_predict_from_files already
    encodes for files. Predictors cache this per instance and fall
    back to the jnp path for unknown losses, so results stay correct either
    way; tests/test_predict_hotpath.py pins the no-dispatch contract."""
    name = getattr(loss, "name", "")
    if name in _IDENTITY_ACTIVATIONS:
        return lambda s: s
    if name == "sigmoid":
        return _np_sigmoid
    if name == "poisson":
        from ..losses import _POISSON_MAX_EXP  # the one clamp, both paths

        return lambda s: np.exp(
            np.minimum(np.asarray(s, np.float64), _POISSON_MAX_EXP)
        )
    if name == "softmax":
        return _np_softmax
    return None
#: reference enum-name aliases (ResultSaveMode.PREDICT_AS_FEATURE prints
#: "label_as_feature", OnlinePredictor.java:55)
SAVE_MODE_ALIASES = {"label_as_feature": "predict_as_feature"}
PREDICT_TYPES = ("value", "leafid")


class OnlinePredictor:
    """Config-driven model server (reference: OnlinePredictor.java).

    Subclasses implement _load_model() and score(features, other); features
    is a {name: value} dict, `other` carries the sample-dependent base score
    for GBST/GBDT models when configured.
    """

    supports_leaf = False
    n_outputs = 1

    def __init__(self, config, fs: Optional[FileSystem] = None):
        if isinstance(config, str):
            config = hocon.load(config)
        self.config = config
        scheme = str(config.get("fs_scheme", "local"))
        self.fs = fs or (
            LocalFileSystem() if scheme in ("local", "") else create_filesystem(scheme)
        )

    # -- core API --------------------------------------------------------

    def score(self, features: Dict[str, float], other=None) -> float:
        raise NotImplementedError

    def scores(self, features: Dict[str, float], other=None) -> List[float]:
        return [self.score(features, other)]

    def _activation(self):
        """Cached numpy_activation(self.loss); None -> jnp fallback. Lazy
        (not in __init__) so subclasses that set self.loss late still work;
        the racy first computation is idempotent, so no lock."""
        act = self.__dict__.get("_np_act", False)
        if act is False:
            act = self.__dict__["_np_act"] = numpy_activation(self.loss)
        return act

    def predict(self, features: Dict[str, float], other=None) -> float:
        s = self.score(features, other)
        act = self._activation()
        if act is not None:
            return float(act(s))
        return float(self.loss.predict(s))

    def predicts(self, features: Dict[str, float], other=None) -> List[float]:
        return [self.predict(features, other)]

    def loss_value(self, features: Dict[str, float], label, other=None) -> float:
        return float(self.loss.loss(self.score(features, other), label))

    def predict_leaf(self, features: Dict[str, float]) -> List[int]:
        raise NotImplementedError(f"{type(self).__name__} has no leaf predict")

    # -- batch helpers ----------------------------------------------------

    def batch_scores(self, rows: Sequence[Dict[str, float]], others=None) -> np.ndarray:
        out = np.empty((len(rows), self.n_outputs), np.float64)
        for i, fmap in enumerate(rows):
            o = others[i] if others is not None else None
            out[i] = self.scores(fmap, o)
        return out if self.n_outputs > 1 else out[:, 0]

    def batch_predicts(self, rows, others=None) -> np.ndarray:
        s = self.batch_scores(rows, others)
        act = self._activation()
        if act is not None:
            return np.asarray(act(s))
        return np.asarray(self.loss.predict(s))


def parse_feature_kvs(text: str, delim) -> Dict[str, float]:
    fmap: Dict[str, float] = {}
    for kv in text.split(delim.features_delim):
        if not kv:
            continue
        name, _, val = kv.partition(delim.feature_name_val_delim)
        fmap[name] = float(val)
    return fmap


class _RowError(Exception):
    pass


def batch_predict_from_files(
    predictor: OnlinePredictor,
    model_name: str,
    file_dir: str,
    need_py_transform: bool = False,
    py_transform_script: str = "",
    result_save_mode: str = "predict_result_only",
    result_file_suffix: str = "_predict",
    max_error_tol: int = 0,
    eval_metric_str: str = "",
    predict_type_str: str = "value",
    K: int = -1,
) -> float:
    """Offline batch prediction (reference: ContinuousOnlinePredictor
    .batchPredictFromFiles:178-330 + Predicts.java:36-54). Writes one
    `<path><suffix>` result file per input file; returns the weighted avg
    loss over labeled rows (0.0 when none)."""
    save_mode = result_save_mode.lower()
    save_mode = SAVE_MODE_ALIASES.get(save_mode, save_mode)
    if save_mode not in SAVE_MODES:
        raise ValueError(f"unknown result_save_mode {result_save_mode!r}")
    predict_type = (predict_type_str or "value").lower()
    if predict_type not in PREDICT_TYPES:
        raise ValueError("predict type invalid! value or leafid")
    if predict_type == "leafid" and not predictor.supports_leaf:
        raise ValueError(f"{model_name} does not support predict type: leafid")

    delim = predictor.params.data.delim
    fs = predictor.fs
    hook = load_transform_hook(py_transform_script) if need_py_transform else None

    multiclass = model_name.lower() == "multiclass_linear"
    if multiclass and K <= 0:
        K = predictor.n_outputs
    eval_set = (
        EvalSet([m for m in eval_metric_str.split(",") if m], K=max(K, 2))
        if eval_metric_str
        else None
    )
    is_gbst = model_name.lower() in ("gbmlr", "gbsdt", "gbhmlr", "gbhsdt")
    is_gbdt = model_name.lower() == "gbdt"
    opt_cfg = predictor.config.get("optimization") or {}
    sample_dep = bool(
        predictor.config.get("sample_dependent_base_prediction", False)
        or (isinstance(opt_cfg, dict) and opt_cfg.get("sample_dependent_base_prediction"))
    )

    total_loss, weight_cnt, errors = 0.0, 0.0, 0
    ev_preds: List = []
    ev_labels: List = []
    ev_weights: List[float] = []

    def stage(line: str) -> dict:
        """Per-row parse + model walk (host numpy). The jnp activation/loss
        is NOT applied here — it runs once per file on the whole score
        matrix, because per-row jnp dispatch is a device round-trip per line
        (the original per-line design took minutes for a 1.6k-row file)."""
        try:
            xsplits = line.split(delim.x_delim)
            weight = float(xsplits[0])
            label_text = xsplits[1].strip()
            fmap = parse_feature_kvs(xsplits[2], delim)
        except (IndexError, ValueError) as e:
            raise _RowError(str(e)) from e

        has_label = len(label_text) > 0
        if not has_label and save_mode != "predict_result_only":
            raise _RowError(f"sample has no label: {line}")

        other = None
        if sample_dep and len(xsplits) > 3:
            # per-sample base score column (reference: ContinuousOnlinePredictor
            # GBST branch; GBDTOnlinePredictor.batchPredictFromFiles:361-369
            # reads a y_delim-split Float[] per class group)
            if is_gbst:
                other = float(xsplits[3])
            elif is_gbdt:
                oinfo = [float(v) for v in xsplits[3].split(delim.y_delim)]
                other = oinfo if len(oinfo) > 1 else oinfo[0]

        st: dict = {"xsplits": xsplits, "weight": weight, "labels": None}
        try:
            if predict_type == "leafid":
                st["preds"] = [int(v) for v in predictor.predict_leaf(fmap)]
                return st
            st["raw"] = np.asarray(predictor.scores(fmap, other), np.float64)
            if has_label:
                linfo = [float(v) for v in label_text.split(delim.y_delim)]
                k = len(st["raw"])
                if multiclass or k > 1:
                    if len(linfo) == 1:
                        labels = [0.0] * max(K, k)
                        labels[int(linfo[0])] = 1.0
                    elif len(linfo) == max(K, k):
                        labels = linfo
                    else:
                        raise _RowError(f"label num must be {max(K, k)} or 1: {line}")
                    st["labels"] = labels
                else:
                    st["labels"] = [linfo[0]]
        except _RowError:
            raise
        except Exception as e:
            raise _RowError(str(e)) from e
        return st

    def fmt(st: dict) -> str:
        xsplits, preds = st["xsplits"], st["preds"]
        pred_text = delim.y_delim.join(repr(p) for p in preds)
        if save_mode == "predict_result_only":
            return pred_text
        if save_mode == "label_and_predict":
            return xsplits[1] + delim.x_delim + pred_text
        extra = delim.features_delim.join(
            f"{model_name}_label_{i}{delim.feature_name_val_delim}{p!r}"
            for i, p in enumerate(preds)
        )
        return (
            xsplits[0] + delim.x_delim + xsplits[1] + delim.x_delim
            + xsplits[2] + delim.features_delim + extra
        )

    hb = obs_heartbeat("predict.batch", every_s=30.0)
    for path in sorted(fs.recur_get_paths([file_dir])):
        staged: List[dict] = []
        with fs.open(path) as f:
            raw_lines: Iterable[str] = list(f)
        with obs_span("predict.score_file", file=path):
            for raw in raw_lines:
                raw = raw.rstrip("\n")
                if not raw.strip():
                    continue
                for line in hook(raw.encode()) if hook is not None else [raw]:
                    try:
                        staged.append(stage(line))
                    except _RowError as e:
                        errors += 1
                        if errors > max_error_tol:
                            raise ValueError(
                                f"max error tolerance exceeded ({errors}): {e}"
                            ) from e
        obs_inc("predict.rows", len(staged))
        hb.beat(file=path, rows=len(staged), errors=errors)

        # batched activation: ONE jnp call per file
        vrows = [s for s in staged if "raw" in s]
        if vrows:
            with obs_span("predict.activate", rows=len(vrows)):
                raws = np.stack([s["raw"] for s in vrows])  # (N, k)
                k = raws.shape[1]
                act = np.asarray(
                    predictor.loss.predict(raws[:, 0] if k == 1 else raws)
                )
                act = act.reshape(len(vrows), -1)
            for s, arow in zip(vrows, act):
                s["preds"] = [float(v) for v in arow]

        # batched loss over labeled rows: ONE jnp call per file
        lrows = [s for s in vrows if s["labels"] is not None]
        if lrows:
            raws_l = np.stack([s["raw"] for s in lrows])
            k = raws_l.shape[1]
            labs = np.asarray([s["labels"] for s in lrows], np.float64)
            lv = np.asarray(
                predictor.loss.loss(
                    raws_l[:, 0] if k == 1 else raws_l,
                    labs[:, 0] if k == 1 else labs,
                )
            ).reshape(-1)
            for s, li in zip(lrows, lv):
                total_loss += s["weight"] * float(li)
                weight_cnt += s["weight"]
                ev_weights.append(s["weight"])
                ev_labels.append(s["labels"] if len(s["labels"]) > 1 else s["labels"][0])
                ev_preds.append(s["preds"] if len(s["preds"]) > 1 else s["preds"][0])

        out_path = path + result_file_suffix
        with fs.open(out_path, "w") as f:
            for line in (fmt(s) for s in staged):
                f.write(line + "\n")
        log.info("predicted %s -> %s", path, out_path)

    if eval_set is not None and ev_preds:
        preds = np.asarray(ev_preds)
        labels = np.asarray(ev_labels)
        weights = np.asarray(ev_weights, np.float32)
        for k, v in eval_set.evaluate(preds, labels, weights).items():
            log.info("eval %s: %.6f", k, v)

    obs_inc("predict.error_lines", errors)
    return total_loss / weight_cnt if weight_cnt > 0 else 0.0
