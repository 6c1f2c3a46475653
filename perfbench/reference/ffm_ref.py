"""Plain reference for a field-aware factorization machine trained by
full-batch L-BFGS.

Imports nothing of the program. It follows the published score (Juan,
Zhuang, Chin, Lin: Field-aware Factorization Machines for CTR Prediction,
RecSys 2016, equation 4, with libffm's first-order term and bias), over the
slots p of a row with feature f_p, field a_p and value x_p:

    s = sum_p w[f_p] x_p + sum_{p<q} <V[f_p, a_q], V[f_q, a_p]> x_p x_q

as the O(width^2 k) double sum over slot pairs: for every pair (p, q) the
latent vector of p's feature for q's field, picked out of the feature's F
vectors by q's field, against the one of q's feature for p's field, the
pairs p < q kept by a triangular mask. Weighted logistic loss, float32
`jax.numpy` at `highest` matmul precision, rows in blocks so that it fits,
the gradient by `jax.value_and_grad`. The flat vector is
[w (n_features)] ++ [V (n_features, F, k)], the bias feature 0 in slot 0.

The outer loop (first step 1/||g||, two-loop recursion, backtracking Wolfe
line search) and the gaps are `fm_ref.py`'s, loaded by its path: they know
the flat vector only by its two leaves.

`compute` is the precision the loss+gradient pass runs in: float32 for the
reference, bfloat16 for the control put in the program's place.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "perfbench_reference_fm_ref",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fm_ref.py"))
_fm_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fm_ref)
follow, gaps = _fm_ref.follow, _fm_ref.gaps


def make_pass(nf: int, n_fields: int, k: int, need_bias: bool, bias_latent: bool,
              block: int, compute=jnp.float32):
    """loss_and_grad(w, idx, val, field, y, wt) over all rows, a block at a
    time."""
    F = n_fields

    def block_loss(w, idx, val, field, y, wt):
        w = w.astype(compute)
        x = val.astype(compute)
        w1 = w[:nf]
        V = w[nf:].reshape(nf, F * k)  # an id's row: F vectors of k, field-major
        if need_bias and not bias_latent:
            V = V.at[0].set(0.0)
        wx = jnp.sum(x * w1[idx], axis=-1)
        # component-major, so that the chip's tiles hold fields and slots on
        # their lanes and not the k = 4 components (32x padding otherwise)
        Vc = jnp.stack([V[:, c::k] for c in range(k)])  # (k, nf, F): V[f, :, c]
        Vr = Vc[:, idx]  # (k, b, width, F): V[f_p, :, c]
        pick = (field[..., None] == jnp.arange(F)).astype(compute)  # (b, width, F)
        # A[c, b, p, q] = V[f_p, a_q, c]
        A = jnp.einsum("cbpf,bqf->cbpq", Vr, pick)
        pair = jnp.sum(A * jnp.swapaxes(A, 2, 3), axis=0) * x[:, :, None] * x[:, None, :]
        width = idx.shape[1]
        upper = jnp.triu(jnp.ones((width, width), compute), 1)  # p < q
        s = (wx + jnp.sum(pair * upper, axis=(1, 2))).astype(jnp.float32)
        per = jnp.log1p(jnp.exp(-jnp.abs(s))) + jnp.maximum(s, 0.0) - s * y
        return jnp.sum(wt * per)

    vg = jax.jit(jax.value_and_grad(block_loss))

    def loss_and_grad(w, idx, val, field, y, wt):
        n = idx.shape[0]
        loss = jnp.zeros((), jnp.float32)
        grad = jnp.zeros_like(w)
        with jax.default_matmul_precision("highest"):
            for lo in range(0, n, block):
                hi = min(lo + block, n)
                l, g = vg(w, idx[lo:hi], val[lo:hi], field[lo:hi], y[lo:hi], wt[lo:hi])
                loss, grad = loss + l, grad + g.astype(jnp.float32)
        return loss, grad

    return loss_and_grad
