"""FM's one parameter table against the plain two-lookup formulation.

`FMModel.scores` gathers first-order weight and latent row of a slot in one
lookup of a (1 + k)-row table; the reference below is the formula written
out with two lookups and its own masks, and shares nothing with the model.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.models.fm import FMModel
from ytklearn_tpu.optimize.blocked import make_value_and_grad

NF, ROWS, WIDTH = 48, 64, 6


def _model(k, need_bias, bias_latent):
    p = CommonParams.from_config({
        "k": k, "bias_need_latent_factor": bias_latent,
        "model": {"data_path": "unused", "need_bias": need_bias},
        "data": {"train": {"data_path": "unused"}}})
    return FMModel(p, NF)


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, NF, size=(ROWS, WIDTH))
    idx[:, 0] = 0  # the bias slot, and a duplicate id in every row
    idx[:, 1] = idx[:, 2]
    val = rng.rand(ROWS, WIDTH).astype(np.float32)
    y = (rng.rand(ROWS) < 0.5).astype(np.float32)
    wt = (rng.rand(ROWS) < 0.9).astype(np.float32)  # some zero-weight rows
    return tuple(jnp.asarray(a) for a in (idx.astype(np.int32), val, y, wt))


def _masks(k, need_bias, bias_latent):
    """1 where a slot of the flat vector [w1 (nf)] ++ [V (nf*k)] is trained."""
    first, sok = int(k[0]) >= 1, int(k[1])
    m1 = np.full((NF,), 1.0 if first else 0.0, np.float32)
    if need_bias:
        m1[0] = 1.0  # the bias is no first-order weight: the switch spares it
    mv = np.ones((NF, sok), np.float32)
    if need_bias and not bias_latent:
        mv[0] = 0.0
    return np.concatenate([m1, mv.reshape(-1)])


def _two_lookup_scores(w, mask, sok, idx, val):
    w = w * mask
    wx = jnp.sum(val * w[:NF][idx], axis=-1)
    if sok == 0:
        return wx
    vx = w[NF:].reshape(NF, sok)[idx] * val[..., None]  # (n, width, k)
    S = jnp.sum(vx, axis=1)
    S2 = jnp.sum(vx * vx, axis=1)
    return wx + 0.5 * jnp.sum(S * S - S2, axis=-1)


CASES = list(itertools.product(
    ([1, 8], [0, 8], [1, 0]),
    ((False, False), (True, False), (True, True)),
    (None, 16),
))


@pytest.mark.parametrize(
    "k,bias,chunk", CASES,
    ids=[f"k{k[0]}_{k[1]}-bias{int(b[0])}{int(b[1])}-chunk{c}" for k, b, c in CASES])
def test_scores_and_gradient_equal_the_two_lookup_formula(k, bias, chunk):
    need_bias, bias_latent = bias
    m = _model(k, need_bias, bias_latent)
    sok = int(k[1])
    assert m.dim == NF * (1 + sok)
    mask = _masks(k, need_bias, bias_latent)
    rng = np.random.RandomState(1)
    # every slot non-zero, masked ones too: the mask has to be the model's
    w = jnp.asarray(rng.randn(m.dim).astype(np.float32) * 0.3)
    idx, val, y, wt = _batch()

    want = _two_lookup_scores(w, jnp.asarray(mask), sok, idx, val)
    np.testing.assert_allclose(m.scores(w, idx, val), want, rtol=2e-6, atol=2e-6)

    def ref_loss(w):
        s = _two_lookup_scores(w, jnp.asarray(mask), sok, idx, val)
        return jnp.sum(wt * jnp.where(wt > 0, m.loss.loss(s, y), 0.0))

    want_l, want_g = jax.value_and_grad(ref_loss)(w)
    vg = make_value_and_grad(m.pure_loss, chunk, m.batch_row_mask)
    got_l, got_g = jax.jit(vg)(w, idx, val, y, wt)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-6)
    scale = float(jnp.max(jnp.abs(want_g)))
    np.testing.assert_allclose(got_g, want_g, rtol=0, atol=4e-6 * scale)
    # a masked slot gets gradient exactly 0, a trained one that a row
    # touches does not
    got_g = np.asarray(got_g)
    assert np.all(got_g[mask == 0] == 0.0)
    assert np.any(got_g[mask == 1] != 0.0)
