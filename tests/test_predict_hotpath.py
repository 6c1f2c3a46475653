"""Predictor hot-path contracts: no per-request jnp dispatch, thread safety.

Satellites of the serving PR: (1) `predict()` must not route every
single-row request through a `loss.predict` jnp call — that is a device
round-trip (dispatch + device->host sync) per request; the cached
numpy activation handles the common losses and the jnp path stays only as
a fallback. (2) The reference OnlinePredictor API is explicitly
thread-safe; N threads hammering `score`/`batch_scores` concurrently must
match sequential results bit-for-bit — a contract we had never pinned.
"""

import concurrent.futures

import numpy as np
import pytest

from serve_models import (
    build_fm,
    build_gbdt,
    build_gbst,
    build_linear,
    build_multiclass,
    request_rows,
)
from ytklearn_tpu.losses import create_loss
from ytklearn_tpu.predict.base import numpy_activation


class _JnpDispatchForbidden(AssertionError):
    pass


def _forbid_jnp(predictor, monkeypatch):
    def _boom(*a, **k):
        raise _JnpDispatchForbidden(
            "loss.predict (jnp) dispatched on the per-request hot path"
        )

    monkeypatch.setattr(predictor.loss, "predict", _boom)


# ---------------------------------------------------------------------------
# numpy activation fast path
# ---------------------------------------------------------------------------


def test_predict_has_no_jax_dispatch(tmp_path, monkeypatch):
    pred, names = build_linear(tmp_path)
    row = request_rows(1, np.random.RandomState(0), names)[0]
    want = pred.predict(row)  # establishes the cached activation
    _forbid_jnp(pred, monkeypatch)
    assert pred.predict(row) == want
    assert pred.predicts(row) == [want]
    out = pred.batch_predicts([row, row])
    np.testing.assert_array_equal(out, [want, want])


def test_gbdt_predict_no_jax_dispatch(tmp_path, monkeypatch):
    pred, names = build_gbdt(tmp_path)
    row = request_rows(1, np.random.RandomState(1), names)[0]
    want = pred.predict(row)
    _forbid_jnp(pred, monkeypatch)
    assert pred.predict(row) == want


def test_multiclass_predicts_no_jax_dispatch(tmp_path, monkeypatch):
    pred, names = build_multiclass(tmp_path)
    row = request_rows(1, np.random.RandomState(2), names)[0]
    want = pred.predicts(row)
    _forbid_jnp(pred, monkeypatch)
    assert pred.predicts(row) == want
    assert sum(want) == pytest.approx(1.0)


def test_thompson_sampling_no_jax_dispatch(tmp_path, monkeypatch):
    pred, names = build_linear(tmp_path)
    row = request_rows(1, np.random.RandomState(3), names)[0]
    pred.predict(row)
    _forbid_jnp(pred, monkeypatch)
    assert 0.0 <= pred.thompson_sampling_predict(row, alpha=0.1) <= 1.0


@pytest.mark.parametrize(
    "loss_name,scores",
    [
        ("sigmoid", [-700.0, -3.2, 0.0, 3.2, 700.0]),
        ("l2", [-1.5, 0.0, 2.25]),
        ("l1", [-1.5, 0.0, 2.25]),
        ("hinge", [-2.0, 0.5]),
        ("poisson", [-2.0, 0.0, 3.0, 50.0]),
    ],
)
def test_numpy_activation_matches_jnp(loss_name, scores):
    loss = create_loss(loss_name)
    act = numpy_activation(loss)
    assert act is not None
    got = np.asarray([float(act(s)) for s in scores])
    want = np.asarray([float(loss.predict(s)) for s in scores])
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-300)


def test_numpy_activation_softmax_matches_jnp():
    loss = create_loss("softmax")
    act = numpy_activation(loss)
    s = np.asarray([[1.0, -2.0, 0.5, 900.0], [0.0, 0.0, 0.0, 0.0]])
    np.testing.assert_allclose(
        np.asarray(act(s)), np.asarray(loss.predict(s)), rtol=1e-12
    )


def test_numpy_activation_unknown_loss_falls_back():
    assert numpy_activation(create_loss("hsoftmax")) is None
    # and the predictor path still works through jnp for such losses
    assert numpy_activation(object()) is None


# ---------------------------------------------------------------------------
# thread safety: concurrent == sequential, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "builder", [build_linear, build_multiclass, build_fm, build_gbdt,
                lambda tp: build_gbst(tp, variant="gbmlr")]
)
def test_predictor_thread_safety_bit_for_bit(tmp_path, builder):
    pred, names = builder(tmp_path)
    rng = np.random.RandomState(42)
    rows = request_rows(40, rng, names)
    sequential = pred.batch_scores(rows)
    seq_single = [pred.scores(r) for r in rows]

    n_threads, n_iters = 8, 5
    failures = []

    def hammer(tid):
        local_rng = np.random.RandomState(tid)
        for _ in range(n_iters):
            if local_rng.rand() < 0.5:
                got = pred.batch_scores(rows)
                if not np.array_equal(got, sequential):
                    failures.append(("batch", tid))
            else:
                i = local_rng.randint(len(rows))
                if pred.scores(rows[i]) != seq_single[i]:
                    failures.append(("single", tid, i))

    with concurrent.futures.ThreadPoolExecutor(n_threads) as ex:
        list(ex.map(hammer, range(n_threads)))
    assert not failures, f"concurrent scoring diverged: {failures[:5]}"


def test_compiled_scorer_thread_safety(tmp_path):
    from ytklearn_tpu.serve import CompiledScorer

    pred, names = build_gbdt(tmp_path)
    scorer = CompiledScorer(pred, ladder=(1, 4, 16))
    rows = request_rows(16, np.random.RandomState(7), names)
    want = scorer.score_batch(rows)
    failures = []

    def hammer(tid):
        for _ in range(5):
            if not np.array_equal(scorer.score_batch(rows), want):
                failures.append(tid)

    with concurrent.futures.ThreadPoolExecutor(6) as ex:
        list(ex.map(hammer, range(6)))
    assert not failures
