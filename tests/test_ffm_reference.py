"""FFMModel against the benchmark's plain reference
(perfbench/reference/ffm_ref.py: the published double sum over slot pairs),
on seeded random weights at small sizes on the CPU. Needs no /root/reference.

Tolerances: both sides are float32. The program sums a row's pair term by
field pairs (F x F x k products after a one-hot aggregation), the reference
by slot pairs (width x width x k), so the two differ by summation order
only: a few float32 roundings (6e-8 each) over up to 1,600 terms a row and a
few hundred rows a sum. 2e-5 relative to the largest magnitude compared
leaves room for that and is far under what a bfloat16 pass does (4e-3 a
product).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.models.ffm import FFMModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 2e-5


def load_ref():
    path = os.path.join(ROOT, "perfbench", "reference", "ffm_ref.py")
    spec = importlib.util.spec_from_file_location("ffm_ref_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_model(nf, F, k, first_order=1, bias_latent=False):
    p = CommonParams()
    p.k = [first_order, k]
    p.bias_need_latent_factor = bias_latent
    p.random.normal_std = 0.2  # pair terms of order one, so they are seen
    return FFMModel(p, nf, n_fields=F)


def criteo_rows(rng, n, nf, width):
    """One feature a field a row: slot 0 the bias (id 0, field 0), slot j
    the j-th column in field j - 1."""
    idx = rng.randint(1, nf, size=(n, width)).astype(np.int32)
    idx[:, 0] = 0
    val = rng.rand(n, width).astype(np.float32)
    val[:, 0] = 1.0
    field = np.broadcast_to(
        np.concatenate([[0], np.arange(width - 1)]).astype(np.int32), (n, width)).copy()
    return idx, val, field


def mixed_rows(rng, n, nf, width, F):
    """Fields that differ by slot and from row to row and repeat within a
    row; some slots are padding (value 0)."""
    idx, val, _ = criteo_rows(rng, n, nf, width)
    field = rng.randint(0, F, size=(n, width)).astype(np.int32)
    field[:, 0] = 0
    val[rng.rand(n, width) < 0.15] = 0.0
    val[:, 0] = 1.0
    return idx, val, field


def batch_of(rng, rows):
    n = rows[0].shape[0]
    y = (rng.rand(n) < 0.4).astype(np.float32)
    wt = (0.5 + rng.rand(n)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in rows + (y, wt))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


CASES = {
    "criteo_shape": dict(nf=300, F=39, k=4, width=40, n=256, mixed=False),
    "fields_by_slot": dict(nf=50, F=3, k=4, width=7, n=300, mixed=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("bias_latent", [False, True])
def test_loss_and_gradient_match_reference(case, bias_latent):
    c = CASES[case]
    rng = np.random.RandomState(7)
    model = make_model(c["nf"], c["F"], c["k"], bias_latent=bias_latent)
    rows = (mixed_rows(rng, c["n"], c["nf"], c["width"], c["F"]) if c["mixed"]
            else criteo_rows(rng, c["n"], c["nf"], c["width"]))
    batch = batch_of(rng, rows)
    w = model.init_weights()
    w[: c["nf"]] = rng.randn(c["nf"]).astype(np.float32) * 0.3
    if bias_latent:  # init zeroes the bias's row; give it one to see it used
        stride = c["F"] * c["k"]
        w[c["nf"] : c["nf"] + stride] = rng.randn(stride).astype(np.float32) * 0.2
    w = jnp.asarray(w)
    loss, grad = jax.jit(jax.value_and_grad(model.pure_loss))(w, *batch)
    ref = load_ref().make_pass(c["nf"], c["F"], c["k"], True, bias_latent, block=100)
    rloss, rgrad = ref(w, *batch)
    assert abs(float(loss) - float(rloss)) <= RTOL * abs(float(rloss))
    nf = c["nf"]
    assert rel(grad[:nf], rgrad[:nf]) <= RTOL  # first-order block
    assert rel(grad[nf:], rgrad[nf:]) <= RTOL  # latent block
    if not bias_latent:  # the bias's latent row is masked: no gradient
        assert not np.any(np.asarray(grad[nf : nf + c["F"] * c["k"]]))


def test_without_first_order():
    """k[0] = 0: the first-order weights but the bias's neither count nor
    get a gradient; the latent block is the reference's at that point."""
    c = CASES["fields_by_slot"]
    rng = np.random.RandomState(11)
    model = make_model(c["nf"], c["F"], c["k"], first_order=0)
    batch = batch_of(rng, mixed_rows(rng, c["n"], c["nf"], c["width"], c["F"]))
    w = model.init_weights()
    w[: c["nf"]] = rng.randn(c["nf"]).astype(np.float32) * 0.3
    loss, grad = jax.jit(jax.value_and_grad(model.pure_loss))(jnp.asarray(w), *batch)
    w_ref = w.copy()
    w_ref[1 : c["nf"]] = 0.0
    ref = load_ref().make_pass(c["nf"], c["F"], c["k"], True, False, block=128)
    rloss, rgrad = ref(jnp.asarray(w_ref), *batch)
    assert abs(float(loss) - float(rloss)) <= RTOL * abs(float(rloss))
    nf = c["nf"]
    assert not np.any(np.asarray(grad[1:nf]))
    assert abs(float(grad[0]) - float(rgrad[0])) <= RTOL * abs(float(rgrad[0]))
    assert rel(grad[nf:], rgrad[nf:]) <= RTOL


def test_without_latent_part():
    """k[1] = 0: a linear model through the one first-order lookup."""
    rng = np.random.RandomState(13)
    model = make_model(60, 5, 0)
    batch = batch_of(rng, mixed_rows(rng, 100, 60, 9, 5))
    w = jnp.asarray(rng.randn(60).astype(np.float32))
    assert model.dim == 60
    s = model.scores(w, *batch[:3])
    np.testing.assert_allclose(
        s, np.sum(np.asarray(batch[1]) * np.asarray(w)[np.asarray(batch[0])], axis=1),
        rtol=1e-5, atol=1e-6)


def test_flat_gradient_layout():
    """An id's F·k latent floats of the gradient land where `model_line`
    reads them and `apply_model_line` writes them, and its first-order
    float at its own index; ids no row holds get none."""
    c = CASES["criteo_shape"]
    nf, F, k = c["nf"], c["F"], c["k"]
    rng = np.random.RandomState(17)
    model = make_model(nf, F, k)
    idx, val, field = criteo_rows(rng, 64, nf, c["width"])
    idx[idx >= nf - 20] -= 20  # the last 20 ids appear in no row
    batch = batch_of(rng, (idx, val, field))
    w = jnp.asarray(model.init_weights())
    grad = np.asarray(jax.jit(jax.grad(model.pure_loss))(w, *batch))
    rgrad = np.asarray(load_ref().make_pass(nf, F, k, True, False, block=64)(w, *batch)[1])
    seen = np.unique(idx)
    stride = F * k
    for i in (int(seen[1]), int(seen[len(seen) // 2]), int(seen[-1])):
        line = model.model_line(f"f{i}", i, grad, None, False).split(",")
        assert len(line) == 2 + stride
        back = np.zeros_like(grad)
        model.apply_model_line(back, i, line)
        lo = nf + i * stride
        np.testing.assert_array_equal(back[lo : lo + stride], grad[lo : lo + stride])
        assert np.any(grad[lo : lo + stride])
        assert rel(grad[lo : lo + stride], rgrad[lo : lo + stride]) <= RTOL
        # V[i, field, :] of the reference's layout: field-major, k minor
        np.testing.assert_allclose(
            grad[lo : lo + stride].reshape(F, k), rgrad[nf:].reshape(nf, F, k)[i],
            rtol=1e-3, atol=RTOL * np.max(np.abs(rgrad)))
    unseen = np.setdiff1d(np.arange(nf), seen)
    assert len(unseen) >= 20
    assert not np.any(grad[unseen])
    assert not np.any(grad[nf:].reshape(nf, stride)[unseen])


def test_score_bytes_and_chunk_from_the_formulation():
    """`score_bytes_per_row` counts what `scores` holds of a row, backward
    included: the gathered rows, the field-pair sums and a cotangent, with
    F·k on the lanes, not k or F alone; the chunk is the budget over it."""
    model = make_model(1 << 18, 39, 4)
    per_row = model.score_bytes_per_row(40)
    assert per_row == 3 * 40 * 256 * 4  # 120 KiB, where k-minor held 1.6 MB forward alone
    chunk = model.suggest_row_chunk(1 << 21, 40)
    assert chunk == 8192  # 1 GiB over 120 KiB, rounded down to a power of two
    assert per_row * chunk <= 1 << 30 < per_row * 2 * chunk
    assert model.suggest_row_chunk(5000, 23) is None  # the demo does not chunk


@pytest.mark.parametrize("latent,width", [(4, 1 + 5 * 4), (0, 1)])
def test_scope_map_names_the_one_gather_and_scatter(latent, width):
    """One lookup a slot: the compiled loss+gradient of a chunk holds one
    gather and one scatter, both under `ffm.gather`, and with a latent part
    something under `ffm.pair`; the gauges say what a model looks up."""
    import re

    from ytklearn_tpu import obs
    from ytklearn_tpu.obs import scopes
    from ytklearn_tpu.optimize.blocked import make_value_and_grad

    obs.configure(enabled=False)
    obs.reset()
    obs.configure(enabled=True)
    try:
        rng = np.random.RandomState(19)
        model = make_model(64, 5, latent)
        assert obs.REGISTRY.gauges["ffm.stat.gather_width"] == width
        assert obs.REGISTRY.gauges["ffm.stat.fields"] == 5
        batch = batch_of(rng, mixed_rows(rng, 32, 64, 9, 5))
        vg = make_value_and_grad(model.pure_loss, 16, model.batch_row_mask, None, "data", 5)

        def ffm_pass(w, *batch):
            return vg(w, *batch)

        prog = scopes.Program(ffm_pass)
        w = jnp.asarray(model.init_weights())
        loss, grad = prog(w, *batch)
        assert np.isfinite(float(loss)) and grad.shape == w.shape
        ops = scopes.scope_map()["jit_ffm_pass"]
        # the CPU compiler leaves gather and scatter as instructions of
        # their own (the TPU's wraps each in a custom fusion)
        text = next(iter(prog._compiled.values())).as_text()
        lookups = {op: set(re.findall(rf"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ {op}\(", text, re.M))
                   for op in ("gather", "scatter")}
        assert len(lookups["gather"]) == 1 and len(lookups["scatter"]) == 1, lookups
        for names in lookups.values():
            assert [ops.get(n) for n in names] == ["ffm.gather"], (names, ops)
        assert ("ffm.pair" in ops.values()) == (latent > 0)
    finally:
        obs.configure(enabled=False, jsonl_path=None)
        obs.reset()
