"""Family adapter: boosted trees through `GBDTTrainer.train(train, test)`,
the trainer built as `cli._train_once` builds it.

A step is one boosted tree. The trainer's host loop enqueues rounds ahead of
the device, so the two boundaries of the window are taken where the device is
drained: set-up ends when the compiled round program has run `warm_steps`
times and its last result is ready; the window closes where the trainer stops
at a round boundary on SIGTERM, the stop a user has, which the harness sends
at `--seconds`: every round already enqueued finishes and counts, then the
time is read, before the emergency checkpoint is written. If training ends by
itself first, the window is the whole train phase. The recorders below wrap
three of the trainer's methods on the instance and change nothing they do.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading

from pb.manifest import ROOT


def make_rows(seed: int, data_seed: int, n: int, n_test: int, F: int):
    """Higgs-shaped rows with a planted nonlinear signal, made on the device
    in one jitted call (bench.py::_gen_gbdt). Not the real Higgs file: there
    is no network. Every seed gets the same rows (`data_seed` of the traffic
    file) in another order, so that the seed does not change the work."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ytklearn_tpu.gbdt.data import GBDTData

    @jax.jit
    def gen(key, order):
        kx, ke = jax.random.split(key)
        X = jax.random.normal(kx, (n + n_test, F), jnp.float32)
        logit = (1.5 * X[:, 0] * X[:, 1] + jnp.sin(X[:, 2] * 2)
                 + 0.8 * (X[:, 3] > 0.5) - 0.5 * X[:, 4] ** 2
                 + 0.3 * X[:, 5] * X[:, 6])
        noise = jax.random.normal(ke, (n + n_test,)) * 0.5
        y = (logit + noise > 0).astype(jnp.float32)
        p_tr = jax.random.permutation(order, n)
        p_te = n + jax.random.permutation(jax.random.fold_in(order, 1), n_test)
        return X[p_tr], y[p_tr], X[p_te], y[p_te]

    X, y, Xt, yt = jax.block_until_ready(gen(
        jax.random.PRNGKey(data_seed), jax.random.PRNGKey(seed % (2**31))))
    names = [f"f{i}" for i in range(F)]

    def mk(X, y):
        return GBDTData(X=X, y=y, weight=np.ones(X.shape[0], np.float32),
                        n_real=X.shape[0], feature_names=names)

    return mk(X, y), mk(Xt, yt)


def build_trainer(run, program: dict):
    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.config.params import GBDTParams
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer
    from ytklearn_tpu.io.fs import create_filesystem

    cfg = hocon.load(os.path.join(ROOT, program["conf"]))
    cfg = hocon.set_path(cfg, "optimization.round_num", int(program["round_num"]))
    for key, name in (("model.data_path", "gbdt.model"),
                      ("model.dict_path", "gbdt.dict"),
                      ("model.feature_importance_path", "gbdt.importance")):
        cfg = hocon.set_path(cfg, key, os.path.join(run.work_dir, name))
    p = GBDTParams.from_config(cfg)
    fs = create_filesystem(str(cfg.get("fs_scheme", "local")))
    kw = {}
    if program.get("hist_precision", "bf16") != "bf16":
        kw["hist_precision"] = program["hist_precision"]  # a control, not a cell
    return GBDTTrainer(p, mesh=None, fs=fs, **kw), p


def train(run, overrides: dict) -> dict:
    import jax

    from ytklearn_tpu import obs
    from ytklearn_tpu.resilience.preempt import Preempted

    program = {**run.cell.config["program"], **overrides}
    sizes = run.cell.sizes
    warm = int(run.cell.traffic["warm_steps"])
    obs.configure(enabled=True)
    obs.health.install_trace_counters()
    train_d, test_d = make_rows(run.seed, int(run.cell.traffic["data_seed"]),
                                int(sizes["train_rows"]),
                                int(sizes["test_rows"]), int(sizes["features"]))
    trainer, params = build_trainer(run, program)
    st = {"calls": 0, "carry": None, "timer": None, "start_round": 0}

    orig_probe = trainer._probe_compile
    orig_preempt = trainer._preempt_checkpoint
    orig_rounds = trainer._run_rounds

    def probe_compile(jit_round, carry, data, start_round):
        compiled = orig_probe(jit_round, carry, data, start_round)

        def timed_round(carry, rnd, key, data):
            out = compiled(carry, rnd, key, data)
            st["calls"] += 1
            st["carry"] = out
            run.boundary(st["calls"])  # enqueued, not finished: the host runs ahead
            if st["calls"] == warm:
                jax.block_until_ready(out[3])
                run.open_window(warm)
                st["timer"] = threading.Timer(
                    run.seconds, os.kill, (os.getpid(), signal.SIGTERM))
                st["timer"].daemon = True
                st["timer"].start()
            return out

        return timed_round

    def wave_rows(wlog, lo, hi):
        """Rows scanned and rows needed by the histogram passes of trees
        [lo, hi), from the round program's wave log."""
        import numpy as np

        wl = np.asarray(wlog[lo:hi])
        used = wl[..., 3] > 0
        run.facts.update(hist_rows_scanned=float(wl[..., 0][used].sum()),
                         hist_rows_needed=float(wl[..., 1][used].sum()))

    def preempt_checkpoint(model, bufs, bins, names, rnd):
        jax.block_until_ready(bufs)
        run.close_window(rnd)
        st["trees"] = rnd
        wave_rows(bufs["wlog"], warm, rnd)
        return orig_preempt(model, bufs, bins, names, rnd)

    def run_rounds(*a, **kw):
        carry = orig_rounds(*a, **kw)  # Preempted passes through
        jax.block_until_ready(carry[3])
        if st["timer"] is not None:
            st["timer"].cancel()
        run.close_window(st["calls"], exhausted=True)
        st["trees"] = st["calls"]
        wave_rows(carry[2]["wlog"], warm, st["calls"])
        return carry

    trainer._probe_compile = probe_compile
    trainer._preempt_checkpoint = preempt_checkpoint
    trainer._run_rounds = run_rounds
    try:
        trainer.train(train=train_d, test=test_d)
    except Preempted:
        pass
    finally:
        if st["timer"] is not None:
            st["timer"].cancel()
    scores, scores_t, _bufs, loss_buf, tloss_buf = st["carry"]
    run.facts.update(trees_total=st["trees"], warm_steps=warm,
                     time_stats={k: v for k, v in trainer.time_stats.items()
                                 if isinstance(v, (int, float))})
    run.gauges.update({f"gbdt.stat.{k}": float(v)
                       for k, v in trainer.time_stats.items()
                       if isinstance(v, (bool, int, float))})
    state = {
        "model_path": params.model.data_path, "trees": st["trees"],
        "loss": loss_buf, "loss_test": tloss_buf, "scores": scores,
        "scores_test": scores_t, "train": train_d, "test": test_d,
    }
    del trainer, st
    return state


def compare(run, state: dict) -> dict:
    """The dumped text model against the plain reference, on every train and
    test row: see reference/gbdt_ref.py. Checked in full: the first and the
    last `check_trees` trees the run grew."""
    import numpy as np

    from pb.manifest import load_module

    ref = load_module("reference", "gbdt_ref")
    limits = run.cell.config["compare"]["limits"]
    model_cfg = run.cell.config["model"]
    k = int(run.cell.config["compare"]["check_trees"])
    T = int(state["trees"])
    with open(state["model_path"]) as f:
        model = ref.parse_model(f.read())
    run.attempted, run.failed = T, T - len(model["trees"])
    if len(model["trees"]) != T:
        return {"trees_missing": (float(abs(T - len(model["trees"]))), 0.0)}
    which = sorted(set(range(min(k, T))) | set(range(max(T - k, 0), T)))
    tr, te = state["train"], state["test"]
    out = ref.follow(model, tr.feature_names, tr.X, tr.y, te.X, te.y, which,
                     lr=float(model_cfg["learning_rate"]), l2=float(model_cfg["l2"]),
                     min_h=float(model_cfg["min_child_hessian_sum"]),
                     quantile_bins=int(model_cfg["quantile_bins"]))
    g = ref.gaps(out, np.asarray(state["loss"])[:T],
                 np.asarray(state["loss_test"])[:T],
                 state["scores"], state["scores_test"])
    run.readings = g
    print("perfbench readings: " + json.dumps({"checked_trees": which, **g}),
          file=sys.stderr)
    return {name: (g[name], float(lim)) for name, lim in limits.items()}


# -- planted faults: the self-checks and controls.py break the timed path
# underneath a run and see `correct` come out false. Never used by a run.
FAULTS = ("state_unchanged", "half_batch", "altered_answer",
          "dropped_features", "coarse_bins")


def plant(fault: str):
    """Break the compiled round program as `GBDTTrainer._probe_compile` hands
    it out; returns the function that mends it."""
    import jax.numpy as jnp

    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    orig = GBDTTrainer._probe_compile

    def probe_compile(self, jit_round, carry, data, start_round):
        compiled = orig(self, jit_round, carry, data, start_round)
        calls = {"n": 0, "data": None}

        def broken(carry, rnd, key, data):
            calls["n"] += 1
            if fault == "half_batch":
                # every second row left out of the histograms
                if calls["data"] is None:
                    calls["data"] = data[:3] + (data[3].at[::2].set(False),) + data[4:]
                return compiled(carry, rnd, key, calls["data"])
            if fault in ("dropped_features", "coarse_bins"):
                if calls["data"] is None:
                    bins = data[0]
                    if fault == "dropped_features":
                        # the histograms never see the first half of the features
                        bins = bins.at[: bins.shape[0] // 2].set(0)
                    else:
                        # neighbouring bins merged in pairs: binned more coarsely
                        bins = bins - (bins & 1)
                    calls["data"] = (bins,) + data[1:]
                return compiled(carry, rnd, key, calls["data"])
            if calls["n"] != 2:
                return compiled(carry, rnd, key, data)
            if fault == "state_unchanged":
                # the second round hands back the scores it was given
                old = (carry[0] + 0.0, None if carry[1] is None else carry[1] + 0.0)
                out = compiled(carry, rnd, key, data)
                return old + tuple(out[2:])
            out = compiled(carry, rnd, key, data)
            bufs = dict(out[2])
            bufs["leaf"] = bufs["leaf"].at[rnd].multiply(1.02)  # altered_answer
            return tuple(out[:2]) + (bufs,) + tuple(out[3:])

        return broken

    GBDTTrainer._probe_compile = probe_compile

    def mend():
        GBDTTrainer._probe_compile = orig

    return mend
