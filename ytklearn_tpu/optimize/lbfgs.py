"""Distributed L-BFGS with OWL-QN and the reference's three line-search modes.

Rebuild of reference optimizer/HoagOptimizer.java:306-1201 as *one jitted
program per iteration*: the line search (each trial = full loss+grad) runs as
a `lax.while_loop` on device, the two-loop recursion unrolled over a history
of m (s, y) pairs kept newest first, and the OWL-QN pseudo-gradient / orthant
projection / direction constraint as elementwise selects. The host loop only
handles convergence checks, eval, and checkpoint dumps — the reference
instead paid a full network allreduce per line-search trial
(HoagOptimizer.lineSearch:1068-1201); here trials stay on-device and data
parallelism rides XLA-inserted psums (rows sharded, w replicated).

Data arrays are threaded through the jitted programs as *arguments*
(`batch`), never closures — closed-over device arrays are captured as
constants at lowering time, which bloats the HLO and makes compiles scale
with data size. Compiled programs are cached per (loss_fn, config, reg
shape), so hyper-search rounds and repeat calls don't recompile.

Semantics kept bit-for-bit where they matter:
  - loss bookkeeping is *weighted sums* (unnormalized), reg scaled by the
    total train weight (calcLossAndGrad:985-1006)
  - OWL-QN pseudo-gradient via partPos/partNeg (:1040-1062)
  - orthant projection in the line search (:1089-1103)
  - direction constraint p=0 where p*g>=0 on L1-regularized slots (:697-705)
  - ys < 1e-60 -> 0.01*yy guard (:678-681)
  - convergence: ||g|| / max(||w||,1) <= eps (:534)
  - line-search failure statuses -1/-2/-3 and revert-to-prev (:1150-1175)
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import (
    gauge as obs_gauge,
    health,
    inc as obs_inc,
    profiler,
    span as obs_span,
    step_span as obs_step_span,
)
from ..obs.scopes import Program

_MODES = {"sufficient_decrease": 0, "wolfe": 1, "strong_wolfe": 2}


@dataclass(frozen=True)
class LBFGSConfig:
    """Mirror of param/LineSearchParams.java:43."""

    m: int = 8
    max_iter: int = 60
    eps: float = 1e-3
    mode: str = "wolfe"
    c1: float = 1e-4
    c2: float = 0.9
    step_decr: float = 0.5
    step_incr: float = 2.1
    ls_max_iter: int = 55
    min_step: float = 1e-16
    max_step: float = 1e18

    @classmethod
    def from_params(cls, lsp) -> "LBFGSConfig":
        return cls(
            m=lsp.lbfgs_m,
            max_iter=lsp.lbfgs_max_iter,
            eps=lsp.lbfgs_eps,
            mode=lsp.mode,
            c1=lsp.c1,
            c2=lsp.c2,
            step_decr=lsp.step_decr,
            step_incr=lsp.step_incr,
            ls_max_iter=lsp.max_iter,
            min_step=lsp.min_step,
            max_step=lsp.max_step,
        )


class LBFGSState(NamedTuple):
    w: jnp.ndarray
    g: jnp.ndarray  # (pseudo-)gradient at w
    loss: jnp.ndarray  # regularized weighted-sum loss
    pure_loss: jnp.ndarray
    step: jnp.ndarray  # initial step for next line search
    S: Tuple[jnp.ndarray, ...]  # m vectors (dim,), newest first
    Y: Tuple[jnp.ndarray, ...]  # m vectors (dim,), newest first
    ys: jnp.ndarray  # (m,), newest first
    hist_len: jnp.ndarray  # how many of the m pairs are real
    ls_status: jnp.ndarray  # >0 ok (trial count), <0 failed
    # trials the last line search made, failed or not: each one loss+gradient
    # pass. An output of the iteration and no input of the next one
    ls_trials: Optional[jnp.ndarray] = None


@dataclass
class LBFGSResult:
    w: jnp.ndarray
    loss: float
    pure_loss: float
    n_iter: int
    status: str
    converged: bool
    state: Optional[LBFGSState] = None  # final state (curvature history for HOAG)


class Reg(NamedTuple):
    """Regularization operands threaded through the jitted programs."""

    l1_vec: jnp.ndarray  # (dim,) — zeros when no L1
    l2_vec: jnp.ndarray  # (dim,)
    g_weight: jnp.ndarray  # scalar total train weight


def _two_loop_core(g, S, Y, ys_arr, hist_len, m: int):
    """-H⁻¹·g via the two-loop recursion over the m newest (s, y) pairs,
    `S[0]` the newest (reference: HoagOptimizer.Hv:904-929; history
    replicated here — on a TPU mesh the dots are local FLOPs, so the
    reference's history-slice sharding + allgather dance is unnecessary at
    these dims; for very large dim shard w/S/Y over the mesh and XLA
    re-inserts the psums). The pairs are m separate vectors, so that an
    iteration adds one pair without rewriting the others (at dim 41M a
    rewritten (m, dim) history is a second 1.3 GB copy of each of S, Y)."""
    p = -g
    alphas = []
    for i in range(m):  # newest first
        valid = i < hist_len
        alpha = jnp.where(valid, jnp.vdot(S[i], p) / ys_arr[i], 0.0)
        p = p - alpha * Y[i]
        alphas.append(alpha)

    p = p * ys_arr[0] / jnp.vdot(Y[0], Y[0])

    for i in reversed(range(m)):  # oldest valid first
        valid = i < hist_len
        beta = jnp.where(valid, jnp.vdot(Y[i], p) / ys_arr[i], 0.0)
        p = p + jnp.where(valid, alphas[i] - beta, 0.0) * S[i]
    return p


@partial(jax.jit, static_argnames=("m",))
def inv_hessian_vp(state: LBFGSState, v, m: int):
    """H⁻¹·v from a converged L-BFGS state's curvature history — the Hv
    call HOAG uses to precondition the test gradient (reference:
    HoagOptimizer.hyperHoagOptimization:822-826 -> Hv:904-929). Falls back
    to identity when no history exists."""
    return jnp.where(
        state.hist_len > 0,
        -_two_loop_core(v, state.S, state.Y, state.ys, state.hist_len, m),
        v,
    )


def _loss_grad(vg_fn, has_l1: bool, w, reg: Reg, batch):
    """calcLossAndGrad equivalent (reference: HoagOptimizer.java:978-1066).
    vg_fn(w, *batch) -> (pure_loss, grad) — plain value_and_grad or the
    row-chunked variant (optimize/blocked.py).
    -> (pure_loss, all_loss, pseudo_grad)."""
    pure, G = vg_fn(w, *batch)
    gw = reg.g_weight
    all_loss = pure + 0.5 * gw * jnp.sum(reg.l2_vec * w * w)
    G = G + gw * reg.l2_vec * w
    if has_l1:
        l1v = reg.l1_vec
        all_loss = all_loss + gw * jnp.sum(l1v * jnp.abs(w))
        sign_or_pos = jnp.where(w != 0.0, jnp.sign(w), 1.0)
        gpos = G + gw * l1v * sign_or_pos
        gneg = jnp.where(w != 0.0, gpos, gpos - 2.0 * gw * l1v)
        pg = jnp.where(gneg > 0.0, gneg, jnp.where(gpos < 0.0, gpos, 0.0))
        G = jnp.where(l1v > 0.0, pg, G)
    return pure, all_loss, G


# program cache: (pure_loss_fn, trace-relevant config fields, has_l1,
# chunking) -> (first_eval, iteration). max_iter/eps only drive the host
# loop and must not key the cache (they'd force pointless recompiles).
# Bounded LRU so a long-lived process sweeping many models doesn't pin
# executables forever.
from collections import OrderedDict

_PROGRAMS: "OrderedDict" = OrderedDict()
_PROGRAMS_MAX = 16


def _trace_key(config: LBFGSConfig):
    return (
        config.m,
        config.mode,
        config.c1,
        config.c2,
        config.step_decr,
        config.step_incr,
        config.ls_max_iter,
        config.min_step,
        config.max_step,
    )


def _build_programs(
    pure_loss_fn,
    config: LBFGSConfig,
    has_l1: bool,
    row_chunk=None,
    row_mask=None,
    mesh=None,
    data_axis="data",
    n_batch=0,
    split=None,
):
    key = (
        pure_loss_fn, _trace_key(config), has_l1, row_chunk, row_mask, mesh, split
    )
    hit = _PROGRAMS.get(key)
    if hit is not None:
        _PROGRAMS.move_to_end(key)
        return hit

    m = config.m
    mode = _MODES[config.mode]
    c1, c2 = config.c1, config.c2
    from .blocked import make_value_and_grad

    vg_fn = make_value_and_grad(
        pure_loss_fn, row_chunk, row_mask, mesh, data_axis, n_batch, split=split
    )
    lg = partial(_loss_grad, vg_fn, has_l1)

    def orthant_project(l1v, w_try, wprev, gprev):
        """reference: lineSearch orthant block :1089-1103."""
        if not has_l1:
            return w_try
        zero_cross = jnp.where(
            wprev != 0.0, w_try * wprev <= 0.0, w_try * gprev >= 0.0
        )
        return jnp.where((l1v > 0.0) & zero_cross, 0.0, w_try)

    def line_search(wprev, gprev, p, step0, loss0, pure0, reg, batch):
        """reference: HoagOptimizer.lineSearch:1068-1201. Returns
        (w, g, loss, pure, status, trials) — status<0: failed (reverted);
        `trials` counts every trial made, those of a failed search too."""
        dginit = jnp.vdot(gprev, p)

        def body(carry):
            step, ls_iter, _, _, _, _, _ = carry
            w_try = orthant_project(reg.l1_vec, wprev + step * p, wprev, gprev)
            pure, loss, g = lg(w_try, reg, batch)
            ls_iter = ls_iter + 1
            dgtest = jnp.vdot(w_try - wprev, gprev)
            dg = jnp.vdot(p, g)

            suff_ok = loss <= loss0 + c1 * dgtest
            wolfe_ok = dg >= c2 * dginit
            strong_ok = dg <= -c2 * dginit
            if mode == 0:
                ok = suff_ok
                factor = config.step_decr
            elif mode == 1:
                ok = suff_ok & wolfe_ok
                factor = jnp.where(~suff_ok, config.step_decr, config.step_incr)
            else:
                ok = suff_ok & wolfe_ok & strong_ok
                factor = jnp.where(
                    ~suff_ok,
                    config.step_decr,
                    jnp.where(~wolfe_ok, config.step_incr, config.step_decr),
                )

            status = jnp.where(
                ok,
                ls_iter,
                jnp.where(
                    step < config.min_step,
                    -1,
                    jnp.where(
                        step > config.max_step,
                        -2,
                        jnp.where(ls_iter >= config.ls_max_iter, -3, 0),
                    ),
                ),
            ).astype(jnp.int32)
            return (step * factor, ls_iter, status, w_try, g, loss, pure)

        init = (
            step0,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32),
            wprev,
            gprev,
            loss0,
            pure0,
        )
        _, trials, status, w, g, loss, pure = lax.while_loop(
            lambda c: c[2] == 0, body, init
        )
        failed = status < 0
        # on failure move back to the previous point (reference :585-589)
        w = jnp.where(failed, wprev, w)
        g = jnp.where(failed, gprev, g)
        loss = jnp.where(failed, loss0, loss)
        pure = jnp.where(failed, pure0, pure)
        return w, g, loss, pure, status, trials

    def first_eval(w, reg, batch):
        pure, loss, g = lg(w, reg, batch)
        return pure, loss, g, jnp.linalg.norm(w), jnp.linalg.norm(g)

    def iteration(state: LBFGSState, reg: Reg, batch):
        """One full L-BFGS iteration: direction from history -> line search
        -> the new (s, y) pair (reference main loop :566-715). Hands back
        the pieces of the next state; `_Iteration` puts them together."""
        wprev, gprev = state.w, state.g
        p = jnp.where(
            state.hist_len > 0,
            _two_loop_core(gprev, state.S, state.Y, state.ys, state.hist_len, m),
            -gprev,
        )
        if has_l1:
            # constrain search direction (reference :697-705)
            p = jnp.where((reg.l1_vec > 0.0) & (p * gprev >= 0.0), 0.0, p)

        w, g, loss, pure, status, trials = line_search(
            wprev, gprev, p, state.step, state.loss, state.pure_loss, reg, batch
        )

        s = w - wprev
        y = g - gprev
        ys = jnp.vdot(y, s)
        yy = jnp.vdot(y, y)
        ys = jnp.where(ys < 1e-60, 0.01 * yy, ys)  # curvature guard (:678-681)
        ys_arr = jnp.concatenate([ys[None], state.ys[:-1]])
        new_len = jnp.minimum(state.hist_len + 1, m).astype(jnp.int32)
        return (
            (w, g, loss, pure, status, trials),
            (s, y, ys_arr, new_len),
            jnp.linalg.norm(w),
            jnp.linalg.norm(g),
        )

    # compiled ahead of time under their own names (`jit_first_eval`,
    # `jit_iteration` on a device trace's module line), the compiled HLO at
    # hand for the scope map (obs/scopes.py)
    programs = (Program(first_eval), _Iteration(iteration))
    _PROGRAMS[key] = programs
    while len(_PROGRAMS) > _PROGRAMS_MAX:
        _PROGRAMS.popitem(last=False)
    return programs


class _Iteration(Program):
    """`iteration(state, reg, batch) -> (new state, ||w||, ||g||)`. The
    jitted step hands back the iteration's own pair (s, y); here, on the
    host, it goes to the head of the history and the oldest pair falls off,
    so that a step allocates one pair and no second copy of S and Y. After a
    failed line search (status < 0) the history stays as it was: the read of
    the status and the trial count, in one fetch, is the sync the caller's
    own reads of `ls_status` and `ls_trials` make next."""

    def __call__(self, state: LBFGSState, reg: Reg, batch):
        # the last search's trials are no input: every call has one signature
        (w, g, loss, pure, status, trials), (s, y, ys_arr, new_len), wnorm, gnorm = (
            super().__call__(state._replace(ls_trials=None), reg, batch)
        )
        S, Y, ys, hist_len = state.S, state.Y, state.ys, state.hist_len
        if int(jax.device_get((status, trials))[0]) > 0:
            S, Y = (s,) + tuple(S[:-1]), (y,) + tuple(Y[:-1])
            ys, hist_len = ys_arr, new_len
        new_state = LBFGSState(
            w=w,
            g=g,
            loss=loss,
            pure_loss=pure,
            step=jnp.ones((), w.dtype),  # step=1 after first iteration (:707)
            S=S,
            Y=Y,
            ys=ys,
            hist_len=hist_len,
            ls_status=status,
            ls_trials=trials,
        )
        return new_state, wnorm, gnorm


def minimize_lbfgs(
    pure_loss_fn: Callable,
    w0: jnp.ndarray,
    config: LBFGSConfig,
    batch: Tuple = (),
    l1_vec: Optional[jnp.ndarray] = None,
    l2_vec: Optional[jnp.ndarray] = None,
    g_weight: float = 1.0,
    callback: Optional[Callable[[int, LBFGSState], bool]] = None,
    row_chunk: Optional[int] = None,
    row_mask: Optional[Tuple[bool, ...]] = None,
    mesh=None,
    data_axis: str = "data",
    split=None,
) -> LBFGSResult:
    """Run distributed L-BFGS/OWL-QN to convergence.

    pure_loss_fn(w, *batch) must return the *weighted-sum* data loss
    (jit-safe; batch arrays may be sharded over a mesh — XLA inserts the
    psums the reference issued by hand at HoagOptimizer.java:1014,1038).
    Pass the SAME function object across calls to reuse compiled programs.

    row_chunk: evaluate loss+grad as a scan over row chunks of this size so
    peak memory is O(chunk) — the reference's blocked-CoreData contract
    (dataflow/CoreData.java:51-52; see optimize/blocked.py). row_mask marks
    which batch elements are row-aligned (default: all). With `mesh`, the
    chunked scan runs per-shard under shard_map over `data_axis` + psum.
    split: `pure_loss_fn` in two parts, `(prepare, loss_p)` with
    `pure_loss_fn(w, *b) == loss_p(prepare(w), *b)`: the chunked scan then
    runs `prepare` and its transpose once a pass instead of once a chunk
    (optimize/blocked.py; a model's `loss_split`). Unused without row_chunk.

    callback(iter, state) runs on host once per iteration (eval/dump hook —
    the reference's per-iteration eval + dump_freq block :605-660); returning
    True stops early.
    """
    dim = w0.shape[0]
    dtype = jnp.asarray(w0).dtype
    has_l1 = l1_vec is not None and bool(jnp.any(jnp.asarray(l1_vec) > 0))
    reg = Reg(
        l1_vec=(
            jnp.zeros((dim,), dtype) if l1_vec is None else jnp.asarray(l1_vec, dtype)
        ),
        l2_vec=(
            jnp.zeros((dim,), dtype) if l2_vec is None else jnp.asarray(l2_vec, dtype)
        ),
        g_weight=jnp.asarray(g_weight, dtype),
    )
    first_eval, iteration = _build_programs(
        pure_loss_fn,
        config,
        has_l1,
        row_chunk=row_chunk,
        row_mask=row_mask,
        mesh=mesh,
        data_axis=data_axis,
        n_batch=len(batch),
        split=split,
    )

    obs_inc("lbfgs.runs")
    # what a run keeps on the device of its own, from shapes: the m (s, y)
    # pairs once the history is full, w, g, the direction and the trial point
    obs_gauge("lbfgs.stat.state_bytes", (2 * config.m + 4) * dim * dtype.itemsize)
    from ..obs import recorder

    recorder.auto_install()  # flight ring for postmortems (no-op when obs off)
    # phase + ledger label: first_eval absorbs the program compiles, so
    # the ytkprof compile ledger names them (and the wall decomposition
    # separates compile-dominated warmup from steady iterations)
    with profiler.phase("lbfgs.first_eval", dim=dim), profiler.LEDGER.program(
        "lbfgs.first_eval",
        sig_fn=lambda: profiler.abstract_signature(w0, reg, batch),
    ):
        pure, loss, g, wnorm, gnorm = first_eval(jnp.asarray(w0, dtype), reg, batch)
        wnorm = max(float(wnorm), 1.0)  # the fetch settles the span
    obs_inc("lbfgs.passes")  # the first evaluation is one data pass
    # one zero vector stands in for every pair not made yet
    no_pair = jnp.zeros((dim,), dtype)
    state = LBFGSState(
        w=jnp.asarray(w0, dtype),
        g=g,
        loss=loss,
        pure_loss=pure,
        step=jnp.asarray(1.0 / max(float(gnorm), 1e-300), dtype),
        S=(no_pair,) * config.m,
        Y=(no_pair,) * config.m,
        ys=jnp.ones((config.m,), dtype),
        hist_len=jnp.asarray(0, jnp.int32),
        ls_status=jnp.asarray(1, jnp.int32),
        ls_trials=jnp.asarray(0, jnp.int32),
    )
    if callback is not None and callback(0, state):
        return _result(state, 0, "callback_stop")
    if float(gnorm) / wnorm <= config.eps:
        return _result(state, 0, "converged_at_init", converged=True)

    it = 0
    status = "max_iter"
    converged = False
    # health sentinels piggyback on the per-iteration ls_status sync: the
    # loss is computed by then, so the fetch is a 4-byte RTT, not a stall.
    # YTK_HEALTH=0 drops both the checks and the fetch (one attribute load).
    health_on = health.enabled()
    guard = health.ProgressGuard("lbfgs", window=10) if health_on else None
    # iterations run inside one ytkprof phase (opt-in capture: the kernel
    # table for the solve comes from here); state/reg/batch shapes are
    # static after warmup, so any ledger entry the loop produces IS an
    # unexpected retrace with its signature attached
    with profiler.phase("lbfgs.iterations", capture=True):
        for it in range(1, config.max_iter + 1):
            # the span's ls_status fetch doubles as the device sync the loop
            # needs anyway — the duration is device-settled for free
            with obs_step_span("lbfgs.iteration", it, it=it) as sp, profiler.LEDGER.program(
                "lbfgs.iteration",
                sig_fn=lambda: profiler.abstract_signature(state, reg, batch),
            ):
                state, wnorm, gnorm = iteration(state, reg, batch)
                ls, trials = int(state.ls_status), int(state.ls_trials)
                # every line-search trial is one loss+gradient pass over
                # the data, those of a search that failed (status -1..-3,
                # the reason) as well
                sp.add(passes=trials, trials=trials, status=ls)
            # the host's part of the step (counters, sentinels, the caller's
            # callback, the convergence test) under a span of that step:
            # while it runs the device has nothing to do
            with obs_span("lbfgs.host", step=it):
                obs_inc("lbfgs.iterations")
                obs_inc("lbfgs.passes", trials)
                if health_on:
                    # after the iteration's span, so a strict escalation's
                    # flight dump carries it completed in its ring
                    loss_val = float(state.loss)
                    if not health.check_loss("lbfgs.loss", loss_val, it=it):
                        status = "nan_loss"
                        break
                    guard.update(loss_val, it=it)
                if ls < 0:
                    obs_inc("lbfgs.ls_failures")
                    status = f"line_search_failed({ls})"
                    break
                if callback is not None and callback(it, state):
                    status = "callback_stop"
                    break
                if float(gnorm) / max(float(wnorm), 1.0) <= config.eps:
                    status = "converged"
                    converged = True
                    break
    return _result(state, it, status, converged)


def _result(state, n_iter, status, converged=False) -> LBFGSResult:
    return LBFGSResult(
        w=state.w,
        loss=float(state.loss),
        pure_loss=float(state.pure_loss),
        n_iter=n_iter,
        status=status,
        converged=converged,
        state=state,
    )
