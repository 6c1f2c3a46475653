"""Family adapter: the convex families through `HoagTrainer.train(ingest)`,
rows handed over as an `IngestResult` made on the device from the seed.

A step is one data pass: one loss+gradient evaluation over all train rows,
line-search trials included. The program's own count is used: one pass for
`lbfgs.first_eval` and, for each iteration, the trials its `ls_status`
reports. Every iteration ends in a device sync (the trainer reads
`ls_status`), and then runs its host callback (test loss, every fifth
iteration the AUC): a boundary is the moment that callback has returned.
Set-up ends at the boundary of iteration `warm_steps`; the window closes at
the first boundary at or after `--seconds`, where the recorder answers the
trainer's callback with "stop" (the path `just_evaluate` takes), so the
trainer dumps the model and hands its weights back as for any finished run.
The recorder wraps `minimize_lbfgs`'s callback and changes nothing it does.
"""

from __future__ import annotations

import json
import os
import sys

from pb.manifest import ROOT


def make_rows(seed: int, data_seed: int, sizes: dict, data: dict):
    """Criteo-shaped padded-ELL rows with a planted linear signal, made on
    the device in one jitted call. Slot 0 is the bias (id 0, value 1). Each
    of the 13 numeric columns is one fixed id with a value in [0, 1). Each of
    the 26 categorical columns draws a rank from a Zipf law (exponent 1,
    rank = floor(exp(u ln(C + 1))), so P(r) = ln(1 + 1/r) / ln(C + 1)) over
    that column's published count of distinct values C, and the pair
    (column, rank) is hashed into the table (murmur3's 32-bit finalizer),
    value 1: a few ids of every column take most of its rows, as in the
    logs. Every seed gets the same rows (`data_seed` of the traffic file) in
    another order, so that the seed does not change the work (the line
    search takes the same trials)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, nt = int(sizes["train_rows"]), int(sizes["test_rows"])
    width, dim = int(sizes["row_width"]), int(sizes["hashed_dim"])
    n_num = int(sizes["numeric_columns"])
    cards = np.asarray(data["categorical_cardinalities"], np.float64)
    if 1 + n_num + len(cards) != width or len(cards) != int(sizes["categorical_columns"]):
        raise SystemExit("perfbench: the columns do not add up to row_width")
    log_card = jnp.asarray(np.log(cards + 1.0), jnp.float32)
    column = jnp.arange(1, width, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)

    def hashed(h):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        return (1 + h % jnp.uint32(dim - 1)).astype(jnp.int32)

    @jax.jit
    def gen(key, order):
        ki, kv, kw, ke = jax.random.split(key, 4)
        rows = n + nt
        u = jax.random.uniform(ki, (rows, len(cards)), jnp.float32)
        rank = jnp.minimum(jnp.floor(jnp.exp(u * log_card)),
                           jnp.asarray(cards, jnp.float32)).astype(jnp.uint32)
        rank = jnp.concatenate([jnp.zeros((rows, n_num), jnp.uint32), rank], axis=1)
        idx = jnp.concatenate([jnp.zeros((rows, 1), jnp.int32),
                               hashed(rank + column[None, :])], axis=1)
        val = jnp.ones((rows, width), jnp.float32)
        val = val.at[:, 1:1 + n_num].set(
            jax.random.uniform(kv, (rows, n_num), jnp.float32))
        w_true = jax.random.normal(kw, (dim,), jnp.float32) * 0.3
        score = jnp.sum(val * w_true[idx], axis=1)
        y = (score + 0.5 * jax.random.normal(ke, (rows,)) > 0).astype(jnp.float32)
        wt = jnp.ones((rows,), jnp.float32)
        p_tr = jax.random.permutation(order, n)
        p_te = n + jax.random.permutation(jax.random.fold_in(order, 1), nt)
        return ((idx[p_tr], val[p_tr], y[p_tr], wt[:n]),
                (idx[p_te], val[p_te], y[p_te], wt[n:]))

    return jax.block_until_ready(gen(
        jax.random.PRNGKey(data_seed), jax.random.PRNGKey(seed % (2**31))))


def build_trainer(run, program: dict):
    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.io.fs import create_filesystem
    from ytklearn_tpu.train import HoagTrainer

    cfg = hocon.load(os.path.join(ROOT, program["conf"]))
    cfg = hocon.set_path(cfg, "model.data_path",
                         os.path.join(run.work_dir, "convex.model"))
    p = CommonParams.from_config(cfg)
    fs = create_filesystem(str(cfg.get("fs_scheme", "local")))
    return HoagTrainer(p, program["model_name"], mesh=None, fs=fs)


def train(run, overrides: dict) -> dict:
    import time

    import ytklearn_tpu.train as train_mod
    from ytklearn_tpu import obs
    from ytklearn_tpu.io.reader import IngestResult, SparseDataset

    program = {**run.cell.config["program"], **overrides}
    sizes = run.cell.sizes
    warm = int(run.cell.traffic["warm_steps"])
    follow = int(run.cell.config["compare"]["follow_iterations"])
    obs.configure(enabled=True)
    obs.health.install_trace_counters()
    train_b, test_b = make_rows(run.seed, int(run.cell.traffic["data_seed"]), sizes,
                                run.cell.config["data"])
    dim = int(sizes["hashed_dim"])

    def ds(b):
        return SparseDataset(idx=b[0], val=b[1], y=b[2], weight=b[3],
                             n_real=int(b[0].shape[0]), dim=dim)

    names = {"_bias_": 0, **{f"h{i}": i for i in range(1, dim)}}
    ingest = IngestResult(train=ds(train_b), test=ds(test_b), feature_map=names)
    trainer = build_trainer(run, program)
    rec = {"passes": 1, "loss": [], "trials": [], "iters": 0}  # first_eval: a pass
    orig_minimize = train_mod.minimize_lbfgs

    def minimize(*a, callback=None, **kw):
        def recording(it, state):
            if it == 0:
                rec["w0"], rec["g0"] = state.w, state.g
            else:
                ls = int(state.ls_status)
                rec["passes"] += abs(ls) if ls else 0
                rec["trials"].append(ls)
                rec["iters"] = it
            if it <= follow:
                rec["loss"].append(float(state.loss))
            if it == follow:
                rec["w_follow"] = state.w
            rec["w_last"] = state.w
            stop = callback(it, state) if callback is not None else False
            run.boundary(rec["passes"])
            if it == warm:
                run.open_window(rec["passes"])
            elif run.window is not None and run.window.due(time.perf_counter()):
                run.close_window(rec["passes"])
                return True
            return stop

        return orig_minimize(*a, callback=recording, **kw)

    train_mod.minimize_lbfgs = minimize
    try:
        res = trainer.train(ingest=ingest)
    finally:
        train_mod.minimize_lbfgs = orig_minimize
    if run.window is not None and run.window.is_open:
        run.close_window(rec["passes"], exhausted=True)  # ended by itself
    run.facts.update(iterations=rec["iters"], trials=rec["trials"],
                     status=res.status, passes_total=rec["passes"])
    state = {"rec": rec, "w_result": res.w, "train": train_b,
             "g_weight": float(train_b[3].shape[0]), "n_iter": res.n_iter}
    del trainer, ingest, res
    return state


def reference_run(run, state: dict, compute=None) -> dict:
    """The plain reference from the same start, through the first
    `follow_iterations` iterations."""
    import jax.numpy as jnp
    import numpy as np

    from pb.manifest import load_module

    ref = load_module("reference", "fm_ref")
    cfg, sizes = run.cell.config, run.cell.sizes
    nf, k = int(sizes["hashed_dim"]), int(sizes["latent_dim"])
    mdl = cfg["model"]
    init = mdl["init"]
    w0 = np.zeros((nf * (1 + k),), np.float32)
    rng = np.random.RandomState(int(init["seed"]))
    w0[nf:] = (rng.randn(nf * k) * init["std"] + init["mean"]).astype(np.float32)
    if mdl["need_bias"]:
        w0[nf:nf + k] = 0.0
    l2 = np.zeros_like(w0)
    l2[1 if mdl["need_bias"] else 0:nf] = mdl["l2"][0]
    l2[nf:] = mdl["l2"][1]
    pass_fn = ref.make_pass(nf, k, mdl["need_bias"], mdl["bias_need_latent_factor"],
                            int(cfg["compare"]["reference_block_rows"]),
                            compute=compute or jnp.float32)
    n_iter = min(int(cfg["compare"]["follow_iterations"]), int(state["rec"]["iters"]))
    out = ref.follow(pass_fn, w0, state["train"], jnp.asarray(l2),
                     state["g_weight"], n_iter,
                     mdl["line_search"], m=int(sizes["lbfgs_m"]))
    out["w0"] = w0
    return out


def compare(run, state: dict) -> dict:
    import numpy as np

    from pb.manifest import load_module

    ref = load_module("reference", "fm_ref")
    rec = state["rec"]
    limits = run.cell.config["compare"]["limits"]
    nf = int(run.cell.sizes["hashed_dim"])
    run.attempted = int(rec["iters"])
    run.failed = int(sum(1 for t in rec["trials"] if t < 0))
    out = state["ref_out"] = reference_run(run, state)
    prog = {"loss": rec["loss"], "g0": rec["g0"], "w0": rec["w0"],
            "w": rec.get("w_follow", rec["w_last"])}
    g = ref.gaps(prog, out, nf)
    # the weights the trainer hands back are those of its last iteration
    g["handback_gap"] = float(np.max(np.abs(
        np.asarray(state["w_result"]) - np.asarray(rec["w_last"]))))
    g["init_gap"] = float(np.max(np.abs(np.asarray(rec["w0"]) - out["w0"])))
    k = len(out["trials"])
    g["trials_gap"] = float(sum(abs(a - b) for a, b in zip(rec["trials"][:k], out["trials"])))
    run.readings = g
    print("perfbench readings: " + json.dumps(
        {**g, "trials_prog": rec["trials"], "trials_ref": out["trials"],
         "ref_passes": out["passes"]}), file=sys.stderr)
    return {name: (g[name], float(lim)) for name, lim in limits.items()}


def control_checks(run, state: dict, control: dict) -> dict:
    """The reference at the control's precision put in the program's place:
    the same gaps, read between it and the float32 reference, each beside
    the limit the program is held to (what it hands back is its own last
    iterate, so `handback_gap` has nothing to read)."""
    import jax.numpy as jnp

    from pb.manifest import load_module

    ref = load_module("reference", "fm_ref")
    out = reference_run(run, state, compute=getattr(jnp, control["compute"]))
    g = ref.gaps(out, state["ref_out"], int(run.cell.sizes["hashed_dim"]))
    limits = run.cell.config["compare"]["limits"]
    return {name: (g[name], float(limits[name])) for name in g if name in limits}


# -- planted faults: the self-checks and controls.py break the timed path
# underneath a run and see `correct` come out false. Never used by a run.
FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def plant(fault: str):
    """Returns the function that mends what was broken."""
    import ytklearn_tpu.optimize.lbfgs as lbfgs_mod
    import ytklearn_tpu.train as train_mod

    if fault == "state_unchanged":
        orig = lbfgs_mod._build_programs

        def build(*a, **kw):
            first_eval, iteration = orig(*a, **kw)
            calls = {"n": 0}

            def broken(state, reg, batch):
                calls["n"] += 1
                new, wn, gn = iteration(state, reg, batch)
                if calls["n"] == 2:  # the second iteration hands back its input
                    return state._replace(ls_status=new.ls_status), wn, gn
                return new, wn, gn

            return first_eval, broken

        lbfgs_mod._build_programs = build
        return lambda: setattr(lbfgs_mod, "_build_programs", orig)
    if fault == "half_batch":
        orig = train_mod.minimize_lbfgs

        def minimize(*a, batch=(), g_weight=1.0, **kw):
            # every second row left out, the sum taken over the rest
            wt = batch[-1].at[::2].set(0.0)
            return orig(*a, batch=tuple(batch[:-1]) + (wt,), g_weight=g_weight / 2, **kw)

        train_mod.minimize_lbfgs = minimize
        return lambda: setattr(train_mod, "minimize_lbfgs", orig)
    if fault == "altered_answer":
        orig = train_mod.HoagTrainer.train

        def train_altered(self, ingest=None):
            res = orig(self, ingest)
            res.w = res.w * (1.0 + 1e-3)
            return res

        train_mod.HoagTrainer.train = train_altered
        return lambda: setattr(train_mod.HoagTrainer, "train", orig)
    raise ValueError(fault)
