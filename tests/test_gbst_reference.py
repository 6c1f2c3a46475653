"""The four gradient-boosted soft trees against the benchmark's plain
reference (perfbench/reference/gbst_ref.py, which imports nothing of the
program), on seeded random rows: a loss+gradient pass, the boosting loop
with its fold, and the spans, counters and scopes a tree's turn leaves.
Needs no /root/reference."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu import boost, obs
from ytklearn_tpu.boost import GBSTTrainer
from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.io.reader import IngestResult, SparseDataset
from ytklearn_tpu.models.gbst import GBSTModel

VARIANTS = ("gbmlr", "gbsdt", "gbhmlr", "gbhsdt")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_ref():
    spec = importlib.util.spec_from_file_location(
        "gbst_ref", os.path.join(ROOT, "perfbench", "reference", "gbst_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_ref()


def make_params(K, **over):
    p = CommonParams()
    p.k = K
    p.model.need_bias = True
    p.loss.loss_function = "sigmoid"
    for k, v in over.items():
        setattr(p, k, v)
    return p


def seeded_rows(kind, n, seed):
    """dense: every row carries ids 0..width-1 in order, slot 0 the bias;
    sparse: ids drawn with repeats from a larger vocabulary, short rows
    padded with (id 0, value 0). -> n_features, (idx, val, z, gate mask, y,
    weight), the mask zeroing some features' gates, some weights 0."""
    rng = np.random.RandomState(seed)
    if kind == "dense":
        nf = width = 9
        idx = np.tile(np.arange(width, dtype=np.int32), (n, 1))
        val = rng.randn(n, width).astype(np.float32)
        val[:, 0] = 1.0
    else:
        nf, width = 23, 7
        idx = rng.randint(0, nf, size=(n, width)).astype(np.int32)
        idx[:, 0] = 0
        idx[::3, 2] = idx[::3, 1]  # an id twice in a row
        val = rng.randn(n, width).astype(np.float32)
        val[:, 0] = 1.0
        short = rng.rand(n, width) < 0.2
        short[:, 0] = False
        idx[short], val[short] = 0, 0.0
    z = (rng.randn(n) * 0.3).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    weight[::11] = 0.0
    gmask = (rng.rand(nf) > 0.3).astype(np.float32)
    gmask[0] = 1.0
    return nf, (idx, val, z, gmask, y, weight)


def one_pass(variant, kind, K):
    nf, batch = seeded_rows(kind, 257, 5)
    model = GBSTModel(make_params(K), nf, variant)
    w = (np.random.RandomState(K).randn(model.dim) * 0.3).astype(np.float32)
    loss, grad = jax.jit(jax.value_and_grad(model.pure_loss))(
        jnp.asarray(w), *map(jnp.asarray, batch))
    return nf, batch, w, loss, grad


CASES = [(v, kind, K) for v in VARIANTS for kind in ("dense", "sparse") for K in (4, 8)]


@pytest.mark.parametrize("variant,kind,K", CASES)
def test_pass_agrees_with_the_plain_reference(variant, kind, K):
    nf, batch, w, loss, grad = one_pass(variant, kind, K)
    assert ref.dim(variant, nf, K) == w.shape[0]
    r_loss, r_grad = ref.make_pass(variant, nf, K, block=64)(
        jnp.asarray(w), *map(jnp.asarray, batch))
    assert r_loss.dtype == jnp.float32 and r_grad.dtype == jnp.float32
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=2e-6)
    scale = float(np.max(np.abs(np.asarray(r_grad))))
    np.testing.assert_allclose(np.asarray(grad), np.asarray(r_grad), atol=2e-5 * scale)
    # a masked feature's gates get no gradient, in either
    gates, _ = ref.split(variant, np.asarray(grad), nf, K)
    assert np.all(gates[batch[3] == 0] == 0.0)


# loss (float.hex) and the gradient's bytes (sha1) of `one_pass` at the
# parent commit of PR 35 (c72a04b), before `tree_output` had scopes
PARENT = {
    "gbmlr-dense-4": ("0x1.a7cbaa0000000p+7", "2581aba0d5928c10"),
    "gbmlr-dense-8": ("0x1.a93e280000000p+7", "189a95f72b0a0ee6"),
    "gbmlr-sparse-4": ("0x1.8689fe0000000p+7", "745eb735b40dd2a2"),
    "gbmlr-sparse-8": ("0x1.7de7500000000p+7", "25fd06c357d405a7"),
    "gbsdt-dense-4": ("0x1.98e49c0000000p+7", "168ea26adc495ff4"),
    "gbsdt-dense-8": ("0x1.9b13a40000000p+7", "d80a78f04157047c"),
    "gbsdt-sparse-4": ("0x1.8000580000000p+7", "9d666d25d7dc1839"),
    "gbsdt-sparse-8": ("0x1.86108a0000000p+7", "00bce3886edeadb2"),
    "gbhmlr-dense-4": ("0x1.aa8c0e0000000p+7", "3997223699bca134"),
    "gbhmlr-dense-8": ("0x1.a5caa00000000p+7", "15b3feb9717170d8"),
    "gbhmlr-sparse-4": ("0x1.8324b00000000p+7", "afee47cdd4cc1ff0"),
    "gbhmlr-sparse-8": ("0x1.79fb2c0000000p+7", "d0d13e58708f29cd"),
    "gbhsdt-dense-4": ("0x1.9933e00000000p+7", "d63b633a6c6842e2"),
    "gbhsdt-dense-8": ("0x1.9aecec0000000p+7", "0e4469ae80dc8af4"),
    "gbhsdt-sparse-4": ("0x1.80a0820000000p+7", "cdbeed983e460b1f"),
    "gbhsdt-sparse-8": ("0x1.8375080000000p+7", "b0de1bce22d43048"),
}


@pytest.mark.parametrize("variant,kind,K", CASES)
def test_pass_is_the_parents_bit_for_bit(variant, kind, K):
    """The scopes name the operations and change none: loss and gradient
    equal, bit for bit, what the un-scoped `tree_output` gave."""
    _, _, _, loss, grad = one_pass(variant, kind, K)
    assert loss.dtype == jnp.float32 and grad.dtype == jnp.float32
    got = (float(loss).hex(), hashlib.sha1(np.asarray(grad).tobytes()).hexdigest()[:16])
    assert got == PARENT[f"{variant}-{kind}-{K}"]


def test_heap_probabilities_are_the_programs():
    from ytklearn_tpu.models.gbst import heap_leaf_probs

    a = jnp.asarray(np.random.RandomState(1).randn(13, 7), jnp.float32)
    got, want = ref.heap_probs(a), heap_leaf_probs(jax.nn.sigmoid(a))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.sum(-1)), np.ones(13), rtol=1e-6)


# -- the boosting loop ------------------------------------------------------

LS = {"c1": 1e-4, "c2": 0.9, "step_decr": 0.5, "step_incr": 2.1, "max_iter": 55}
INIT = {"mode": "normal", "mean": 0.0, "std": 0.01, "leaf_range": [-2.0, 2.0]}


def boosting_case(variant, tmp_path, rates=(1.0, 1.0), n=600, n_test=150, K=4):
    nf, (idx, val, _z, _m, y, weight) = seeded_rows("dense", n + n_test, 17)
    weight = np.where(weight == 0, 1.0, weight).astype(np.float32)
    p = make_params(K, tree_num=2, learning_rate=0.5,
                    instance_sample_rate=rates[0], feature_sample_rate=rates[1])
    p.model.data_path = str(tmp_path / f"{variant}.model")
    p.loss.l2 = [1e-3]
    p.line_search.lbfgs_max_iter = 3

    def ds(lo, hi):
        return SparseDataset(idx=idx[lo:hi], val=val[lo:hi], y=y[lo:hi],
                             weight=weight[lo:hi], n_real=hi - lo, dim=nf)

    names = {"_bias_": 0, **{f"f{i}": i for i in range(1, nf)}}
    ingest = IngestResult(train=ds(0, n), test=ds(n, n + n_test), feature_map=names)
    return p, ingest, nf, K


def record_fits(monkeypatch):
    """Every fit's losses, as `minimize_lbfgs`'s callback sees them."""
    fits, orig = [], boost.minimize_lbfgs

    def minimize(*a, callback=None, **kw):
        fits.append([])
        return orig(*a, callback=lambda it, st: fits[-1].append(float(st.loss)) or False, **kw)

    monkeypatch.setattr(boost, "minimize_lbfgs", minimize)
    return fits


def reference_loop(variant, p, ingest, nf, K, trees, iters, lr):
    """The reference's own boosting loop: per tree its masks and its start,
    `follow` for `iters` iterations, then the fold."""
    tr, te = ingest.train, ingest.test
    g_weight = float(np.sum(tr.weight))
    base = 0.0  # the score of a base prediction of 0.5
    z, z_t = np.full(tr.n, base, np.float32), np.full(te.n, base, np.float32)
    masks = ref.Masks(p.random.seed, tr.n, nf, p.instance_sample_rate,
                      p.feature_sample_rate, True)
    l2 = jnp.asarray(ref.l2_vector(variant, nf, K, True, p.loss.l2[0]))
    pass_fn = ref.make_pass(variant, nf, K, block=256)
    out = []
    for t in range(trees):
        keep, feat = masks.next()
        w0 = ref.init_weights(variant, nf, K, True, p.random.seed, t, INIT)
        batch = tuple(jnp.asarray(a) for a in
                      (tr.idx, tr.val, z, feat, tr.y, tr.weight * keep))
        fit = ref.follow(pass_fn, w0, batch, l2, g_weight, iters, LS, m=p.line_search.lbfgs_m)
        w = fit["w"]
        z = z + lr * np.asarray(ref.tree_output(variant, nf, K, 256, w, tr.idx, tr.val, feat))
        z_t = z_t + lr * np.asarray(ref.tree_output(variant, nf, K, 256, w, te.idx, te.val, feat))
        out.append({"loss": fit["loss"], "w": np.asarray(w), "feat": feat,
                    "train_loss": ref.mean_loss(z, tr.y, tr.weight, g_weight),
                    "test_loss": ref.mean_loss(z_t, te.y, te.weight, float(np.sum(te.weight)))})
    return out, z, g_weight


@pytest.mark.parametrize("variant,rates,chunk", [
    ("gbmlr", (1.0, 1.0), None), ("gbsdt", (0.9, 0.7), None), ("gbhmlr", (0.9, 0.7), None),
    ("gbhsdt", (1.0, 1.0), None),
    # a chunked fit and fold: 600 and 150 rows padded to whole chunks of 128
    # once, at set-up, the masks' draws still counted over the 600
    ("gbmlr", (0.9, 0.7), 128), ("gbhsdt", (1.0, 1.0), 128)])
def test_two_trees_follow_the_references_loop(variant, rates, chunk, tmp_path, monkeypatch):
    if chunk:
        monkeypatch.setenv("YTK_ROW_CHUNK", str(chunk))
    p, ingest, nf, K = boosting_case(variant, tmp_path, rates)
    fits = record_fits(monkeypatch)
    obs.configure(enabled=True)
    obs.reset()
    try:
        res = GBSTTrainer(p, variant).train(ingest=ingest)
        folds = [s for s in obs.spans_between(float("-inf"), float("inf"))
                 if s["name"] == "gbst.fold"]
        gauges = obs.snapshot()["gauges"]
    finally:
        obs.configure(enabled=False)
    want, z_ref, g_weight = reference_loop(variant, p, ingest, nf, K, 2, 3, 0.5)
    assert res.n_trees == 2 and len(fits) == 2 and len(folds) == 2
    if chunk:
        assert gauges["gbst.stat.row_chunk"] == chunk
        assert gauges["gbst.stat.chunks_per_pass"] == -(-600 // chunk)
    tr = ingest.train
    z_dumped = np.zeros(tr.n, np.float32)
    for t in range(2):
        # each iteration's loss, the loss the tree ended on, the ensemble's
        np.testing.assert_allclose(fits[t], want[t]["loss"], rtol=2e-5)
        np.testing.assert_allclose(res.per_tree_loss[t], want[t]["loss"][-1] / g_weight, rtol=2e-5)
        np.testing.assert_allclose(folds[t]["args"]["train_loss"], want[t]["train_loss"], rtol=2e-5)
        np.testing.assert_allclose(folds[t]["args"]["test_loss"], want[t]["test_loss"], rtol=2e-5)
        # the dumped tree is the fitted one (masked gates dumped as zeros)
        with open(f"{p.model.data_path}/tree-{t:05d}/model-00000") as f:
            w = ref.parse_tree(f.read(), variant, ingest.feature_map, K)
        gates, _ = ref.split(variant, w, nf, K)
        assert np.all(gates[want[t]["feat"] == 0] == 0.0)
        np.testing.assert_allclose(w, want[t]["w"] * (w != 0), atol=2e-4)
        z_dumped = z_dumped + 0.5 * np.asarray(ref.tree_output(
            variant, nf, K, 256, w, tr.idx, tr.val, np.ones(nf, np.float32)))
    # the folded score, through the dumped trees, and the loss at it
    np.testing.assert_allclose(z_dumped, z_ref, atol=2e-4)
    np.testing.assert_allclose(
        res.train_loss, ref.mean_loss(z_dumped, tr.y, tr.weight, g_weight), rtol=2e-5)
    np.testing.assert_allclose(res.test_loss, want[1]["test_loss"], rtol=2e-5)


def test_a_trees_turn_in_spans_counters_and_scopes(tmp_path):
    p, ingest, nf, K = boosting_case("gbmlr", tmp_path, n=300, n_test=60)
    obs.configure(enabled=True)
    obs.reset()
    try:
        GBSTTrainer(p, "gbmlr").train(ingest=ingest)
        spans = obs.spans_between(float("-inf"), float("inf"))
        snap = obs.snapshot()
        scope_map = obs.scopes.scope_map()
    finally:
        obs.configure(enabled=False)
    by_id = {s["id"]: s for s in spans}
    root = [s for s in spans if s["name"] == "train.run"]
    trees = sorted((s for s in spans if s["name"] == "gbst.tree"), key=lambda s: s["start"])
    assert len(root) == 1 and [t["step"] for t in trees] == [0, 1]
    assert all(t["parent"] == root[0]["id"] for t in trees)
    for t in trees:
        kids = sorted((s for s in spans if s["parent"] == t["id"]), key=lambda s: s["start"])
        names = [s["name"] for s in kids]
        assert names[0] == "gbst.masks" and names[-2:] == ["gbst.fold", "gbst.dump"]
        fit = kids[1:-2]
        assert [s["name"] for s in fit] == ["lbfgs.first_eval", "lbfgs.iterations"]
        its = [s for s in spans if s["name"] == "lbfgs.iteration" and s["parent"] == fit[1]["id"]]
        assert [s["step"] for s in sorted(its, key=lambda s: s["start"])] == [1, 2, 3]
        assert all(s["step"] is not None for s in kids)
        fold = kids[-2]
        assert {"train_loss", "test_loss"} <= set(fold["args"])
    # every lbfgs span of the run lies under a tree
    for s in spans:
        if s["name"].startswith("lbfgs.") and s["parent"] in by_id:
            top = s
            while top["parent"] in by_id and top["name"] != "gbst.tree":
                top = by_id[top["parent"]]
            assert top["name"] == "gbst.tree"
    c, g = snap["counters"], snap["gauges"]
    assert c["gbst.trees"] == 2 and c["lbfgs.runs"] == 2
    assert c["lbfgs.iterations"] == 6 and c["lbfgs.passes"] >= 8
    assert g["gbst.stat.k"] == K and g["gbst.stat.stride"] == 2 * K - 1
    assert g["gbst.stat.row_chunk"] == g["blocked.stat.row_chunk"] == 300
    assert g["gbst.stat.chunks_per_pass"] == g["blocked.stat.chunks_per_pass"] == 1
    assert g["blocked.stat.prepared"] == 0
    # both scopes are named in the fit's program, and the fold's
    for module in ("jit_iteration", "jit_gbst_fold"):
        under = set(scope_map[module].values())
        assert {"gbst.lookup", "gbst.mixture"} <= under, (module, under)
    # the gather's transpose (the scatter-add) lies under the lookup's scope
    hlo_scopes = scope_map["jit_iteration"]
    assert any(sc == "gbst.lookup" and "scatter" in op for op, sc in hlo_scopes.items()) or \
        sum(1 for sc in hlo_scopes.values() if sc == "gbst.lookup") >= 2


def test_sigterm_at_a_tree_boundary_closes_the_spans(tmp_path, monkeypatch):
    """The guard's flag is read at the top of a tree: the run ends in
    `Preempted` with tree 0 dumped, and every span it opened is closed."""
    import signal

    from ytklearn_tpu.resilience import Preempted, PreemptionGuard

    p, ingest, nf, K = boosting_case("gbmlr", tmp_path, n=300, n_test=60)
    orig = boost.minimize_lbfgs

    def minimize(*a, **kw):
        signal.raise_signal(signal.SIGTERM)  # arrives during tree 0's fit
        return orig(*a, **kw)

    monkeypatch.setattr(boost, "minimize_lbfgs", minimize)
    monkeypatch.setenv("YTK_FLIGHT_DIR", str(tmp_path / "flight"))
    obs.configure(enabled=True)
    obs.reset()
    try:
        with pytest.raises(Preempted):
            GBSTTrainer(p, "gbmlr").train(ingest=ingest)
        spans = obs.spans_between(float("-inf"), float("inf"))
        snap = obs.snapshot()
        open_spans = obs.current_span()
    finally:
        obs.configure(enabled=False)
    # the guard has handed the signal back (to the flight recorder's hook)
    assert not isinstance(getattr(signal.getsignal(signal.SIGTERM), "__self__", None),
                          PreemptionGuard)
    assert open_spans is None
    names = [s["name"] for s in spans]
    assert names.count("gbst.tree") == 1 and "gbst.preempt" in names and "train.run" in names
    assert snap["counters"]["gbst.trees"] == 1 and snap["counters"]["preempt.exits"] == 1
    assert os.path.exists(f"{p.model.data_path}/tree-00000/model-00000")
    assert not os.path.exists(f"{p.model.data_path}/tree-00001")
