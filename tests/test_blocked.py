"""Blocked (row-chunked) evaluation == unchunked (optimize/blocked.py).

The reference trains FM/FFM on arbitrarily large partitions by walking
blocked CoreData storage (reference dataflow/CoreData.java:51-52,
optimizer/FMHoagOptimizer.java:88); the TPU rebuild must match that
contract: chunked loss/grad/score evaluation is mathematically identical
to whole-batch evaluation, on one device and on a mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.models.ffm import FFMModel
from ytklearn_tpu.models.fm import FMModel
from ytklearn_tpu.models.gbst import GBSTModel
from ytklearn_tpu.models.linear import LinearModel
from ytklearn_tpu.optimize import LBFGSConfig, minimize_lbfgs
from ytklearn_tpu.optimize.blocked import (
    blocked_rows,
    chunked_sum,
    chunked_value_and_grad,
    mesh_blocked_rows,
    mesh_chunked_sum,
    mesh_chunked_value_and_grad,
    suggest_chunk,
)


def _fm_fixture(n=301, nf=64, width=7, k=4, seed=3):
    """Non-divisible n exercises the zero-pad path."""
    rng = np.random.RandomState(seed)
    p = CommonParams()
    p.k = [1, k]
    p.model.need_bias = True
    p.loss.loss_function = "sigmoid"
    model = FMModel(p, nf)
    idx = rng.randint(0, nf, size=(n, width)).astype(np.int32)
    val = rng.rand(n, width).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    weight = np.ones(n, np.float32)
    w = jnp.asarray(model.init_weights())
    batch = tuple(jnp.asarray(a) for a in (idx, val, y, weight))
    return model, w, batch


def test_chunked_value_and_grad_matches_fm():
    model, w, batch = _fm_fixture()
    l0, g0 = jax.value_and_grad(model.pure_loss)(w, *batch)
    for chunk in (32, 100, 301, 512):
        l1, g1 = jax.jit(chunked_value_and_grad(model.pure_loss, chunk))(w, *batch)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-5)


def test_chunked_sum_and_blocked_rows_match():
    model, w, batch = _fm_fixture()
    l0 = float(model.pure_loss(w, *batch))
    p0 = np.asarray(model.predicts(w, *batch))
    l1 = float(jax.jit(chunked_sum(model.pure_loss, 64))(w, *batch))
    p1 = np.asarray(jax.jit(blocked_rows(model.predicts, 64))(w, *batch))
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    assert p1.shape == p0.shape
    np.testing.assert_allclose(p1, p0, atol=1e-6)


def test_chunked_gbst_row_mask():
    """GBST batch carries a per-feature gate mask that must NOT be chunked."""
    rng = np.random.RandomState(11)
    n, nf, width = 157, 40, 5
    p = CommonParams()
    p.k = 4
    p.model.need_bias = True
    p.loss.loss_function = "sigmoid"
    model = GBSTModel(p, nf, "gbmlr")
    idx = rng.randint(0, nf, size=(n, width)).astype(np.int32)
    val = rng.rand(n, width).astype(np.float32)
    z = rng.randn(n).astype(np.float32) * 0.1
    gmask = (rng.rand(nf) > 0.3).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    weight = np.ones(n, np.float32)
    w = jnp.asarray(model.init_weights())
    batch = tuple(jnp.asarray(a) for a in (idx, val, z, gmask, y, weight))

    l0, g0 = jax.value_and_grad(model.pure_loss)(w, *batch)
    cvg = chunked_value_and_grad(model.pure_loss, 32, model.batch_row_mask)
    l1, g1 = jax.jit(cvg)(w, *batch)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-5)


def test_mesh_chunked_value_and_grad(mesh8):
    """shard_map + local chunk scan + psum == single-device whole batch."""
    from ytklearn_tpu.parallel.mesh import equal_row_target, put_row_sharded

    model, w, batch = _fm_fixture(n=296)  # 296 = 8 * 37
    l0, g0 = jax.value_and_grad(model.pure_loss)(w, *batch)

    target = equal_row_target(296, mesh8)
    pad = target - 296

    def padrows(a):
        a = np.asarray(a)
        if pad:
            a = np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a

    sharded = tuple(put_row_sharded(padrows(a), mesh8) for a in batch)
    mvg = mesh_chunked_value_and_grad(
        model.pure_loss, 16, None, mesh8, "data", len(batch)
    )
    l1, g1 = jax.jit(mvg)(w, *sharded)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-5)


def test_mesh_eval_variants(mesh8):
    """mesh_chunked_sum / mesh_blocked_rows == whole-batch single device."""
    from ytklearn_tpu.optimize.blocked import mesh_blocked_rows, mesh_chunked_sum
    from ytklearn_tpu.parallel.mesh import put_row_sharded

    model, w, batch = _fm_fixture(n=296)  # divisible by 8
    l0 = float(model.pure_loss(w, *batch))
    p0 = np.asarray(model.predicts(w, *batch))
    sharded = tuple(put_row_sharded(np.asarray(a), mesh8) for a in batch)
    l1 = float(
        jax.jit(mesh_chunked_sum(model.pure_loss, 16, None, mesh8, "data", 4))(
            w, *sharded
        )
    )
    p1 = np.asarray(
        jax.jit(mesh_blocked_rows(model.predicts, 16, None, mesh8, "data", 4))(
            w, *sharded
        )
    )
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_allclose(p1, p0, atol=1e-6)


@pytest.mark.parametrize("hoisted", [False, True], ids=["fn_a_chunk", "prepare_a_pass"])
def test_minimize_lbfgs_chunked_matches(hoisted):
    """Full L-BFGS runs land on the same optimum chunked vs not, with the
    model's `prepare` inside the scan or hoisted out of it."""
    model, w0, batch = _fm_fixture(n=240)
    cfg = LBFGSConfig(max_iter=15, m=5)
    zeros = jnp.zeros((model.dim,), jnp.float32)

    r0 = minimize_lbfgs(
        model.pure_loss, w0, cfg, batch=batch, l1_vec=zeros, l2_vec=zeros,
        g_weight=240.0,
    )
    r1 = minimize_lbfgs(
        model.pure_loss, w0, cfg, batch=batch, l1_vec=zeros, l2_vec=zeros,
        g_weight=240.0, row_chunk=64,
        split=model.loss_split if hoisted else None,
    )
    # chunking changes float summation order, so trajectories drift over
    # 15 iterations — exact loss/grad equality is asserted per-evaluation
    # above; here both runs must land on the same optimum basin
    np.testing.assert_allclose(r1.loss, r0.loss, rtol=2e-2)
    # the first iterates are the same iterates: one evaluation's round-off
    # apart, not another trajectory
    r0s = minimize_lbfgs(
        model.pure_loss, w0, LBFGSConfig(max_iter=2, m=5), batch=batch,
        l1_vec=zeros, l2_vec=zeros, g_weight=240.0,
    )
    r1s = minimize_lbfgs(
        model.pure_loss, w0, LBFGSConfig(max_iter=2, m=5), batch=batch,
        l1_vec=zeros, l2_vec=zeros, g_weight=240.0, row_chunk=64,
        split=model.loss_split if hoisted else None,
    )
    np.testing.assert_allclose(np.asarray(r1s.w), np.asarray(r0s.w), atol=1e-5)


# -- prepare(w) once a pass: the split of optimize/blocked.py ---------------


def _rows(n, nf, width, seed):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, nf, size=(n, width)).astype(np.int32)
    val = rng.rand(n, width).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    # a few zero-weight rows, as ingest pads with
    weight = (rng.rand(n) > 0.05).astype(np.float32)
    return rng, idx, val, y, weight


def _split_fixture(kind, n=301):
    """(model, w, batch): FM with a latent part, FM without, FFM, and a model
    that declares no `prepare` (linear). Dimensions are chosen so that no
    other array of a chunk has the flat vector's shape."""
    p = CommonParams()
    p.model.need_bias = True
    p.loss.loss_function = "sigmoid"
    if kind == "fm":
        nf, width = 53, 7
        p.k = [1, 4]
        model = FMModel(p, nf)
    elif kind == "fm_first_order":
        nf, width = 53, 7
        p.k = [1, 0]
        model = FMModel(p, nf)
    elif kind == "ffm":
        nf, width = 47, 6
        p.k = [1, 3]
        model = FFMModel(p, nf, 5)
    else:
        nf, width = 53, 7
        model = LinearModel(p, nf, dense=False)
    rng, idx, val, y, weight = _rows(n, nf, width, seed=17)
    if kind == "ffm":
        field = rng.randint(0, 5, size=(n, width)).astype(np.int32)
        batch = (idx, val, field, y, weight)
    else:
        batch = (idx, val, y, weight)
    w = model.init_weights()
    if kind in ("fm_first_order", "linear"):
        w = (rng.randn(model.dim) * 0.1).astype(np.float32)
    else:
        w[: model.v_start] = rng.randn(model.v_start) * 0.1
    return model, jnp.asarray(w), tuple(jnp.asarray(a) for a in batch)


SPLIT_KINDS = ["fm", "fm_first_order", "ffm", "linear"]


def test_split_is_declared_by_the_model():
    for kind in ("fm", "fm_first_order", "ffm"):
        model, w, batch = _split_fixture(kind, n=9)
        prepare, loss_p = model.loss_split
        np.testing.assert_array_equal(
            np.asarray(loss_p(prepare(w), *batch)),
            np.asarray(model.pure_loss(w, *batch)),
        )
        assert model.predicts_split[0] == prepare
    model, _, _ = _split_fixture("linear", n=9)
    assert model.prepare is None
    assert model.loss_split is None and model.predicts_split is None


@pytest.mark.parametrize("kind", SPLIT_KINDS)
@pytest.mark.parametrize("chunk", [32, 301, 512])
def test_hoisted_value_and_grad(kind, chunk):
    """prepare hoisted == whole batch (today's tolerances) == prepare inside
    the scan (float32 round-off of an add)."""
    model, w, batch = _split_fixture(kind)
    l0, g0 = jax.value_and_grad(model.pure_loss)(w, *batch)
    l1, g1 = jax.jit(chunked_value_and_grad(model.pure_loss, chunk))(w, *batch)
    l2, g2 = jax.jit(
        chunked_value_and_grad(model.pure_loss, chunk, split=model.loss_split)
    )(w, *batch)
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g0), atol=1e-5)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-6, atol=1e-6)
    # masked slots (the bias's latent row) get exactly 0, as inside the scan
    np.testing.assert_array_equal(np.asarray(g2) == 0, np.asarray(g1) == 0)


@pytest.mark.parametrize("kind", SPLIT_KINDS)
def test_hoisted_sum_and_rows(kind):
    model, w, batch = _split_fixture(kind)
    l0 = float(model.pure_loss(w, *batch))
    p0 = np.asarray(model.predicts(w, *batch))
    l1 = float(jax.jit(chunked_sum(model.pure_loss, 64))(w, *batch))
    p1 = np.asarray(jax.jit(blocked_rows(model.predicts, 64))(w, *batch))
    l2 = float(
        jax.jit(chunked_sum(model.pure_loss, 64, split=model.loss_split))(w, *batch)
    )
    p2 = np.asarray(
        jax.jit(blocked_rows(model.predicts, 64, split=model.predicts_split))(
            w, *batch
        )
    )
    np.testing.assert_allclose(l2, l0, rtol=1e-5)
    assert p2.shape == p0.shape
    np.testing.assert_allclose(p2, p0, atol=1e-6)
    # the forward pass is the same arithmetic on the same table
    assert l2 == l1
    np.testing.assert_array_equal(p2, p1)


@pytest.mark.parametrize("kind", SPLIT_KINDS)
def test_mesh_hoisted(kind, mesh8):
    """Per shard: prepare, scan, pull back, then the one psum."""
    from ytklearn_tpu.parallel.mesh import put_row_sharded

    model, w, batch = _split_fixture(kind, n=296)  # 8 * 37
    nb = len(batch)
    l0, g0 = jax.value_and_grad(model.pure_loss)(w, *batch)
    p0 = np.asarray(model.predicts(w, *batch))
    sharded = tuple(put_row_sharded(np.asarray(a), mesh8) for a in batch)
    args = (16, None, mesh8, "data", nb)
    l1, g1 = jax.jit(mesh_chunked_value_and_grad(model.pure_loss, *args))(w, *sharded)
    l2, g2 = jax.jit(
        mesh_chunked_value_and_grad(model.pure_loss, *args, split=model.loss_split)
    )(w, *sharded)
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g0), atol=1e-5)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-6, atol=1e-6)
    s2 = float(
        jax.jit(mesh_chunked_sum(model.pure_loss, *args, split=model.loss_split))(
            w, *sharded
        )
    )
    r2 = np.asarray(
        jax.jit(mesh_blocked_rows(model.predicts, *args, split=model.predicts_split))(
            w, *sharded
        )
    )
    np.testing.assert_allclose(s2, float(l0), rtol=1e-5)
    np.testing.assert_allclose(r2, p0, atol=1e-6)


def _parent_chunked_value_and_grad(fn, chunk):
    """The scan as it stood before the split existed: value_and_grad of
    `fn(w, chunk)` in the body, the gradient carried in w's layout."""
    from ytklearn_tpu.optimize.blocked import _stack_chunks

    def run(w, *batch):
        xs, _ = _stack_chunks(batch, chunk)

        def body(carry, ch):
            l, g = jax.value_and_grad(fn)(w, *ch)
            return (carry[0] + l, carry[1] + g), None

        init = (jnp.zeros((), w.dtype), jnp.zeros_like(w))
        (loss, grad), _ = jax.lax.scan(body, init, xs)
        return loss, grad

    return run


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


@pytest.mark.parametrize("kind", ["fm", "ffm"])
def test_scan_body_never_sees_the_flat_vector(kind):
    """With a declared `prepare` nothing in the chunk loop has the flat
    vector's shape: not as the scan's operand, not inside its body."""
    model, w, batch = _split_fixture(kind)
    dim = (model.dim,)
    assert all(a.shape != dim for a in batch)

    def scans(fn):
        jaxpr = jax.make_jaxpr(fn)(w, *batch).jaxpr
        return [e for e in _all_eqns(jaxpr) if e.primitive.name == "scan"]

    def flat_operands(scan):
        seen = [v for v in scan.invars if getattr(v.aval, "shape", None) == dim]
        for eqn in _all_eqns(scan.params["jaxpr"].jaxpr):
            seen += [v for v in eqn.invars if getattr(v.aval, "shape", None) == dim]
        return seen

    for fn in (
        chunked_value_and_grad(model.pure_loss, 64, split=model.loss_split),
        chunked_sum(model.pure_loss, 64, split=model.loss_split),
        blocked_rows(model.predicts, 64, split=model.predicts_split),
    ):
        (scan,) = scans(fn)
        assert flat_operands(scan) == []
    # the check can fail: inside the scan, prepare reads the flat vector
    (scan,) = scans(chunked_value_and_grad(model.pure_loss, 64))
    assert flat_operands(scan)


@pytest.mark.parametrize("kind", ["fm", "ffm"])
def test_chunk_gradient_is_summed_before_it_meets_the_carry(kind):
    """The chunk's gradient passes an optimization barrier before the add
    into the carry, so the compiler cannot make the carry the scatter-add's
    operand (one float32 running sum for every update of a pass: seen on
    the chip, not reproducible on the CPU)."""
    model, w, batch = _split_fixture(kind)
    fn = chunked_value_and_grad(model.pure_loss, 64, split=model.loss_split)
    jaxpr = jax.make_jaxpr(fn)(w, *batch).jaxpr
    (scan,) = [e for e in _all_eqns(jaxpr) if e.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    barriers = [e for e in body.eqns if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == 1
    (g,) = barriers[0].outvars
    users = [e for e in body.eqns if g in e.invars]
    assert [e.primitive.name for e in users] == ["add"]


@pytest.mark.parametrize("kind", ["linear", "fm"])
def test_no_split_traces_the_parent_program(kind):
    """A model that declares no `prepare` (and any function handed in
    without a split) is traced to the scan as it always was."""
    model, w, batch = _split_fixture(kind)
    split = model.loss_split if kind == "linear" else None
    assert split is None
    new = jax.make_jaxpr(chunked_value_and_grad(model.pure_loss, 64, split=split))
    old = jax.make_jaxpr(_parent_chunked_value_and_grad(model.pure_loss, 64))
    assert str(new(w, *batch)) == str(old(w, *batch))


def test_suggest_chunk(monkeypatch):
    monkeypatch.delenv("YTK_ROW_CHUNK", raising=False)
    monkeypatch.delenv("YTK_CHUNK_BUDGET_MB", raising=False)
    # fits budget -> no chunking
    assert suggest_chunk(1000, 1024) is None
    # 2M rows x 80KB >> 1GiB -> power-of-two chunk under budget
    c = suggest_chunk(2_000_000, 80 << 10)
    assert c is not None and c & (c - 1) == 0
    assert c * (80 << 10) <= 1 << 30
    # env override wins
    monkeypatch.setenv("YTK_ROW_CHUNK", "4096")
    assert suggest_chunk(2_000_000, 80 << 10) == 4096
    # env override larger than n -> disabled
    assert suggest_chunk(1000, 80 << 10) is None


def test_fm_suggest_hint():
    p = CommonParams()
    p.k = [1, 8]
    model = FMModel(p, 1 << 18)
    # the exact BENCH_r04 OOM shape: 2M x 39, k=8 must chunk
    assert model.suggest_row_chunk(2_000_000, 39) is not None
    # demo-scale FM must not chunk
    assert model.suggest_row_chunk(5000, 30) is None
