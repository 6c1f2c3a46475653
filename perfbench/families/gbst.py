"""Family adapter: the gradient-boosted soft trees through
`GBSTTrainer(params, variant).train(ingest)`, rows handed over as an
`IngestResult` made on the device from the seed.

The job is many `minimize_lbfgs` calls, one a tree, and the folds between
them. A step is one data pass: one loss+gradient evaluation over all train
rows, line-search trials included, by the program's own count: one pass for
a tree's first evaluation and, for each iteration, the trials its
`ls_status` reports. The two forward evaluations of a fold are no steps;
their seconds stay in the window. The recorder wraps `boost.minimize_lbfgs`,
lives across trees and changes nothing the fits do.

The window holds a fixed set of whole trees, whatever the clock. A tree
boundary is the moment the callback of a tree's first evaluation has
returned: every earlier tree is fitted, folded and dumped, this tree's masks
are drawn and its weights re-drawn, and the device is drained (the trainer
has read the first evaluation's norms). Set-up ends at the boundary of tree
`warm_trees` (at least 1: tree 0, its fold and tree 1's first evaluation,
which `correct` compares, are set-up); the window closes at the boundary of
tree `warm_trees + window_trees` (`warm_trees + trace_trees` in a traced run,
whose profiler keeps only so many device events), not at a boundary set by
`--seconds`. So every run holds the same fits, folds, dumps and mask draws,
and fits the same trees in all: the job's failed searches, which grow with
the trees a job holds (from about the sixth tree on every fit ends in one),
are the program's to change, not the clock's. A faster program has a
shorter window over the same work. The iteration boundaries in between go to
`run.boundary` for placing a stall and open or close nothing.

What a window held is counted here and printed in the result line
(`window`): `passes`, `iterations` (line searches that ended well), `trees`,
`failed_searches` and `trials_per_iteration` (passes but the trees' first
evaluations, over iterations). A fit whose `LBFGSResult.status` reads
`line_search_failed(...)` is one failed search: the run's `failed` counts
them over the whole job, `attempted` every search begun. The passes of a
failed search cannot be counted from outside the program: `optimize/lbfgs.py`
leaves its loop before the callback, and its status carries -1..-3, the
reason, not the trials (some fifty: the line search's `max_iter` is 55); they are in
the window's seconds and in none of its steps.

The stop. Answering the callback with "stop" ends one tree's fit, not the
job: `boost.py` folds that tree and starts the next. So the job is ended the
way a user's is: once the window has closed and the clock is read, at that
same boundary of tree `warm_trees + window_trees`, the recorder raises
SIGTERM, which the trainer's preemption guard defers to the next tree
boundary, and answers "stop"; the trainer folds and dumps the tree it was
on, that tree stopped after its first evaluation, and leaves through
`Preempted` before any later tree is begun.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys

from pb.manifest import ROOT, load_module


def make_rows(seed: int, data_seed: int, sizes: dict):
    """HIGGS-shaped rows as padded-ELL slots, on the device. The rows and
    labels are `families/gbdt.py::make_rows`'s (loaded by its path, left as
    it is); every row is then laid out as the same `row_width` slots: slot 0
    the bias (id 0, value 1), slot j feature j (id j, value x_j). All weights
    are 1. -> ((idx, val, y, weight) train, the same for test).

    The order of the train rows is drawn from `data_seed` alone and the seed
    reorders the test rows: a float32 sum over the train rows in another
    order differs in its last digit, a boosted chain of fits amplifies that,
    and from the second tree on two seeds would take other line-search
    trials, so that a window's work, not its speed, would differ between
    them (PERF.md section 2). Every seed fits the same trees."""
    import jax
    import jax.numpy as jnp

    width, F = int(sizes["row_width"]), int(sizes["features"])
    if width != F + 1:
        raise SystemExit("perfbench: row_width is not the features and the bias slot")
    train_d, test_d = load_module("families", "gbdt").make_rows(
        data_seed, data_seed, int(sizes["train_rows"]), int(sizes["test_rows"]), F)

    @jax.jit
    def reordered(X, y, key):
        order = jax.random.permutation(key, X.shape[0])
        return X[order], y[order]

    test_d.X, test_d.y = reordered(test_d.X, test_d.y, jax.random.PRNGKey(seed % (2**31)))

    @jax.jit
    def slots(X):
        n = X.shape[0]
        idx = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), (n, width))
        val = jnp.concatenate([jnp.ones((n, 1), jnp.float32), X], axis=1)
        return idx, val, jnp.ones((n,), jnp.float32)

    def laid_out(d):
        idx, val, wt = slots(d.X)
        d.X = None  # the matrix is in `val` now
        return idx, val, d.y, wt

    return jax.block_until_ready((laid_out(train_d), laid_out(test_d)))


def check_program() -> None:
    """Stops the run at once where the program folds a tree over all rows in
    one piece: the gathered (rows, width, stride) intermediate of
    `tree_output` is then rows x 16 KiB (172 GB at this cell's size), which
    no chip holds. The fold that scans row chunks came with
    `optimize.blocked.make_rows` in `boost.py`."""
    import ytklearn_tpu.boost as boost_mod

    if not hasattr(boost_mod, "make_rows"):
        raise SystemExit(
            "perfbench: this program's GBST trainer folds a tree over all rows "
            "at once (boost.py has no chunked fold): it cannot run gbmlr_higgs")


def build_trainer(run, program: dict):
    from ytklearn_tpu.boost import GBSTTrainer
    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.io.fs import create_filesystem

    model_path = os.path.join(run.work_dir, "gbst.model")
    for path in (model_path, model_path + "_dict"):
        shutil.rmtree(path, ignore_errors=True)  # an earlier run's trees
    cfg = hocon.load(os.path.join(ROOT, program["conf"]))
    cfg = hocon.set_path(cfg, "model.data_path", model_path)
    p = CommonParams.from_config(cfg)
    fs = create_filesystem(str(cfg.get("fs_scheme", "local")))
    return GBSTTrainer(p, program["variant"], mesh=None, fs=fs)


def whole_chunks(trainer, ds):
    """The rows as a job's ingest leaves them for a chunked pass: padded
    with zero-weight rows to whole chunks of the size the program's own
    model asks for at this shape (`suggest_row_chunk`; the trainer pads so
    itself, and finds nothing to pad here). Padded once, here, so that the
    device holds one copy of the rows and not the caller's beside the
    trainer's; `n_real` stays the real rows."""
    from ytklearn_tpu.models.gbst import GBSTModel

    chunk = GBSTModel(trainer.params, ds.dim, trainer.variant).suggest_row_chunk(
        ds.n, int(ds.idx.shape[1]))
    return ds if chunk is None else ds.pad_rows_to(-(-ds.n // chunk) * chunk)


def feature_names(sizes: dict) -> dict:
    return {"_bias_": 0, **{f"f{i}": i + 1 for i in range(int(sizes["features"]))}}


def train(run, overrides: dict) -> dict:
    import time

    import ytklearn_tpu.boost as boost_mod
    from ytklearn_tpu import obs
    from ytklearn_tpu.io.reader import IngestResult, SparseDataset
    from ytklearn_tpu.resilience.preempt import Preempted

    check_program()
    program = {**run.cell.config["program"], **overrides}
    sizes = run.cell.sizes
    warm = int(run.cell.traffic["warm_trees"])
    if warm < 1:
        raise SystemExit("perfbench: warm_trees under 1: tree 0, its fold and tree 1's "
                         "first evaluation are compared and belong to set-up")
    held = int(run.cell.traffic["trace_trees" if run.trace_on else "window_trees"])
    if held < 1:
        raise SystemExit("perfbench: a window of under one tree (window_trees, trace_trees)")
    follow = int(run.cell.config["compare"]["follow_iterations"])
    obs.configure(enabled=True)
    obs.health.install_trace_counters()
    train_b, test_b = make_rows(run.seed, int(run.cell.traffic["data_seed"]), sizes)
    dim = int(sizes["row_width"])

    def ds(b):
        return SparseDataset(idx=b[0], val=b[1], y=b[2], weight=b[3],
                             n_real=int(b[0].shape[0]), dim=dim)

    trainer = build_trainer(run, program)
    train_ds, test_ds = (whole_chunks(trainer, ds(b)) for b in (train_b, test_b))
    # one copy of the rows on the device: the trainer's, the reference's
    train_b, test_b = ((d.idx, d.val, d.y, d.weight) for d in (train_ds, test_ds))
    ingest = IngestResult(train=train_ds, test=test_ds, feature_map=feature_names(sizes))
    del train_ds, test_ds
    rec = Recorder()
    orig_minimize = boost_mod.minimize_lbfgs

    def end_job() -> None:
        guard = trainer._guard
        if guard is None or not guard.installed:
            raise SystemExit("perfbench: the trainer runs under no preemption guard "
                             "(YTK_PREEMPT=0?): the job cannot be ended as a user's is")
        rec.stopped = True
        signal.raise_signal(signal.SIGTERM)  # the guard sets its flag; nothing else

    def minimize(*a, callback=None, **kw):
        tree = len(rec.fits)
        fit = {"loss": [], "trials": [], "iters": 0}
        rec.fits.append(fit)

        def recording(it, state):
            if it == 0:
                rec.passes += 1  # a tree's first evaluation is a pass
                fit["w0"], fit["g0"] = state.w, state.g
            else:
                ls = int(state.ls_status)
                rec.passes += abs(ls) if ls else 0
                rec.iterations += 1
                fit["trials"].append(ls)
                fit["iters"] = it
            if it <= follow:
                fit["loss"].append(float(state.loss))
            if it == follow:
                fit["w_follow"] = state.w
            fit["w_last"] = state.w
            stop = callback(it, state) if callback is not None else False
            run.boundary(rec.passes)
            if it != 0:
                return stop
            # a tree boundary, trees [0, tree) done: the only place the
            # window opens or closes
            if tree == warm:
                run.open_window(rec.passes, work=held, unit="trees", units_done=tree)
                rec.at_open = rec.counts(tree)
            elif run.window is not None and run.window.due(time.perf_counter(), tree):
                run.close_window(rec.passes, units_done=tree)
                rec.at_close = rec.counts(tree)
            if rec.at_close is not None and not rec.stopped:
                end_job()
                return True
            return stop

        res = orig_minimize(*a, callback=recording, **kw)
        if str(res.status).startswith("line_search_failed"):
            rec.failed_trees.append(tree)
        return res

    boost_mod.minimize_lbfgs = minimize
    t_train = time.perf_counter()
    try:
        trainer.train(ingest=ingest)
    except Preempted:
        pass
    finally:
        boost_mod.minimize_lbfgs = orig_minimize
    if run.window is not None and run.window.is_open:
        run.close_window(rec.passes, exhausted=True)  # ended by itself
        rec.at_close = rec.counts(len(rec.fits))
    folds = sorted((s for s in obs.spans_between(t_train, float("inf"))
                    if s["name"] == "gbst.fold"), key=lambda s: s["start"])
    run.window_counts = rec.window_counts()
    run.facts.update(
        run.window_counts, trees_held=[rec.at_open["trees"], rec.at_close["trees"]],
        trees_started=len(rec.fits), passes_total=rec.passes,
        failed_trees=rec.failed_trees,
        iterations_a_tree=[f["iters"] for f in rec.fits],
        trials_a_tree=[sum(abs(t) for t in f["trials"]) for f in rec.fits],
        trials_tree_0=rec.fits[0]["trials"],
        fold_s=[s["end"] - s["start"] for s in folds],
        trees_in_window=run.counters_window.get("gbst.trees", 0.0))
    state = {"rec": rec, "train": train_b, "test": test_b,
             "folds": [s["args"] for s in folds],
             "model_path": trainer.params.model.data_path,
             "seed": int(trainer.params.random.seed)}
    del trainer, ingest
    return state


class Recorder:
    """What the wrapper around `boost.minimize_lbfgs` has seen of the job so
    far: passes, iterations whose line search ended well, a record a fit
    (`fits`, by tree) and the trees whose fit ended in a failed search.
    `at_open` and `at_close` are `counts` at the window's two tree
    boundaries."""

    def __init__(self):
        self.passes = self.iterations = 0
        self.fits, self.failed_trees = [], []
        self.at_open = self.at_close = None
        self.stopped = False

    def counts(self, tree: int) -> dict:
        """At the boundary of `tree`: trees [0, tree) are done and this
        tree's first evaluation is counted."""
        return {"trees": tree, "passes": self.passes, "iterations": self.iterations,
                "failed_searches": len(self.failed_trees)}

    def window_counts(self) -> dict:
        """The five counts that say whether two windows held the same work."""
        if self.at_open is None or self.at_close is None:
            raise SystemExit("perfbench: the job never reached the window's tree boundaries")
        held = {k: self.at_close[k] - self.at_open[k] for k in self.at_open}
        trials = held["passes"] - held["trees"]  # a first evaluation a tree is no trial
        held["trials_per_iteration"] = (
            trials / held["iterations"] if held["iterations"] else None)
        return held


def _reference(run):
    """The reference module and what it is told of the configuration."""
    ref = load_module("reference", "gbst_ref")
    cfg, sizes = run.cell.config, run.cell.sizes
    mdl = cfg["model"]
    return ref, {
        "variant": mdl["variant"], "nf": int(sizes["row_width"]), "K": int(sizes["k"]),
        "block": int(cfg["compare"]["reference_block_rows"]), "mdl": mdl,
        "m": int(sizes["lbfgs_m"]),
    }


def reference_pass(run, state: dict, compute=None) -> dict:
    """The plain reference from the configuration's own start of tree 0 and
    z = the base score, through the first `follow_iterations` iterations."""
    import jax.numpy as jnp
    import numpy as np

    ref, c = _reference(run)
    mdl, v, nf, K = c["mdl"], c["variant"], c["nf"], c["K"]
    idx, val, y, wt = state["train"]
    n = int(idx.shape[0])
    masks = ref.Masks(state["seed"], n, nf, mdl["instance_sample_rate"],
                      mdl["feature_sample_rate"], mdl["need_bias"])
    keep, feat = masks.next()
    w0 = ref.init_weights(v, nf, K, mdl["need_bias"], state["seed"], 0, mdl["init"])
    base = float(-np.log(1.0 / mdl["uniform_base_prediction"] - 1.0))
    batch = (idx, val, jnp.full((n,), base, jnp.float32), jnp.asarray(feat), y,
             wt * jnp.asarray(keep))
    l2 = jnp.asarray(ref.l2_vector(v, nf, K, mdl["need_bias"], mdl["l2"]))
    pass_fn = ref.make_pass(v, nf, K, c["block"], compute=compute or jnp.float32)
    n_iter = min(int(run.cell.config["compare"]["follow_iterations"]),
                 int(state["rec"].fits[0]["iters"]))
    g_weight = float(jnp.sum(wt))
    out = ref.follow(pass_fn, w0, batch, l2, g_weight, n_iter, mdl["line_search"], m=c["m"])
    out.update(w0=w0, base=base, feat=feat, masks=masks, l2=l2, g_weight=g_weight,
               pass_fn=pass_fn)
    return out


def reference_fold(run, state: dict, out: dict, compute=None) -> dict:
    """From the DUMPED tree 0: the tree's output over all train and test
    rows, z_1 = base + lr * output, the ensemble's loss at z_1 on both, and
    tree 1's first-evaluation loss at z_1 from the reference's own draw of
    tree 1's start and masks."""
    import jax.numpy as jnp
    import numpy as np

    ref, c = _reference(run)
    mdl, v, nf, K = c["mdl"], c["variant"], c["nf"], c["K"]
    with open(os.path.join(state["model_path"], "tree-00000", "model-00000")) as f:
        w_tree = ref.parse_tree(f.read(), v, feature_names(run.cell.sizes), K)
    ones = jnp.ones((nf,), jnp.float32)  # a dump holds the masked gates as zeros
    got = {"w_tree": w_tree}
    for name, (idx, val, y, wt) in (("train", state["train"]), ("test", state["test"])):
        t = ref.tree_output(v, nf, K, c["block"], jnp.asarray(w_tree), idx, val, ones,
                            compute=compute or jnp.float32)
        z1 = out["base"] + mdl["learning_rate"] * t
        got[name + "_loss"] = ref.mean_loss(z1, y, wt, float(jnp.sum(wt)))
        if name == "train":
            got["z1"] = z1
    if len(state["rec"].fits) > 1 and compute is None:
        idx, val, y, wt = state["train"]
        keep, feat = out["masks"].next()
        w1 = ref.init_weights(v, nf, K, mdl["need_bias"], state["seed"], 1, mdl["init"])
        pure, _ = out["pass_fn"](jnp.asarray(w1), idx, val, got["z1"], jnp.asarray(feat), y,
                                 wt * jnp.asarray(keep))
        got["next_loss"] = float(pure + 0.5 * out["g_weight"] * jnp.sum(out["l2"] * w1 * w1))
        got["next_w0"] = w1
    return got


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def compare(run, state: dict) -> dict:
    """The pass (tree 0's first evaluation and two iterations: each loss,
    the first gradient and the weights' change by block), the fold (the
    program's ensemble losses after tree 0 and tree 1's first loss against
    the reference's at the dumped tree's z_1) and the hand-back (the dumped
    tree against the recorder's last weights of tree 0)."""
    import numpy as np

    ref, c = _reference(run)
    v, nf, K = c["variant"], c["nf"], c["K"]
    rec = state["rec"]
    limits = run.cell.config["compare"]["limits"]
    fit0 = rec.fits[0]
    # every line search the job began, and those that failed (a fit's status)
    run.failed = len(rec.failed_trees)
    run.attempted = rec.iterations + run.failed
    out = state["ref_out"] = reference_pass(run, state)
    g = ref.gaps(v, {"loss": fit0["loss"], "g0": fit0["g0"], "w0": fit0["w0"],
                     "w": fit0.get("w_follow", fit0["w_last"])}, out, nf, K)
    g["init_gap"] = float(np.max(np.abs(np.asarray(fit0["w0"]) - out["w0"])))
    k = len(out["trials"])
    g["trials_gap"] = float(sum(abs(a - b) for a, b in zip(fit0["trials"][:k], out["trials"])))
    missing = 1e30  # the run never crossed the tree boundary
    g.update(fold_loss_gap=missing, fold_test_loss_gap=missing,
             next_tree_loss_gap=missing, handback_gap=missing)
    if state["folds"] and len(rec.fits) > 1:
        fold = state["ref_fold"] = reference_fold(run, state, out)
        prog = state["folds"][0]
        dumped_as = ref.masked(v, np.asarray(fit0["w_last"]), out["feat"], nf, K)
        g.update(
            fold_loss_gap=rel(prog["train_loss"], fold["train_loss"]),
            fold_test_loss_gap=rel(prog["test_loss"], fold["test_loss"]),
            next_tree_loss_gap=rel(rec.fits[1]["loss"][0], fold["next_loss"]),
            next_init_gap=float(np.max(np.abs(
                np.asarray(rec.fits[1]["w0"]) - fold["next_w0"]))),
            handback_gap=float(np.max(np.abs(fold["w_tree"] - dumped_as))))
    run.readings = g
    print("perfbench readings: " + json.dumps(
        {**g, "trials_prog": fit0["trials"], "trials_ref": out["trials"],
         "ref_passes": out["passes"]}), file=sys.stderr)
    return {name: (g[name], float(lim)) for name, lim in limits.items()}


def control_checks(run, state: dict, control: dict) -> dict:
    """The reference at the control's precision put in the program's place:
    the pass's gaps and the fold's two losses (the dumped tree's output
    computed at that precision), read between it and the float32 reference,
    each beside the limit the program is held to."""
    import jax.numpy as jnp

    ref, c = _reference(run)
    compute = getattr(jnp, control["compute"])
    out = reference_pass(run, state, compute=compute)
    g = ref.gaps(c["variant"], out, state["ref_out"], c["nf"], c["K"])
    if "ref_fold" in state:
        low, fold = reference_fold(run, state, out, compute=compute), state["ref_fold"]
        g.update(fold_loss_gap=rel(low["train_loss"], fold["train_loss"]),
                 fold_test_loss_gap=rel(low["test_loss"], fold["test_loss"]))
    limits = run.cell.config["compare"]["limits"]
    return {name: (g[name], float(limits[name])) for name in g if name in limits}


# -- planted faults: the self-checks and controls.py break the timed path
# underneath a run and see `correct` come out false. Never used by a run.
FAULTS = ("state_unchanged", "half_batch", "altered_answer", "fold_skipped")


def plant(fault: str):
    """Returns the function that mends what was broken."""
    import ytklearn_tpu.boost as boost_mod
    import ytklearn_tpu.optimize.lbfgs as lbfgs_mod
    from ytklearn_tpu.models.gbst import GBSTModel

    if fault == "state_unchanged":
        orig = lbfgs_mod._build_programs

        def build(*a, **kw):
            first_eval, iteration = orig(*a, **kw)
            calls = {"n": 0}

            def broken(state, reg, batch):
                calls["n"] += 1
                new, wn, gn = iteration(state, reg, batch)
                if calls["n"] == 2:  # a tree's second iteration hands back its input
                    return state._replace(ls_status=new.ls_status), wn, gn
                return new, wn, gn

            return first_eval, broken

        lbfgs_mod._build_programs = build
        return lambda: setattr(lbfgs_mod, "_build_programs", orig)
    if fault == "half_batch":
        orig = boost_mod.minimize_lbfgs

        def minimize(*a, batch=(), g_weight=1.0, **kw):
            # every second row left out of the fit, the sum taken over the rest
            wt = batch[-1].at[::2].set(0.0)
            return orig(*a, batch=tuple(batch[:-1]) + (wt,), g_weight=g_weight / 2, **kw)

        boost_mod.minimize_lbfgs = minimize
        return lambda: setattr(boost_mod, "minimize_lbfgs", orig)
    if fault == "altered_answer":
        orig = GBSTModel.dump_tree

        def dump_altered(self, fs, w, *a, **kw):
            return orig(self, fs, w * (1.0 + 1e-3), *a, **kw)

        GBSTModel.dump_tree = dump_altered
        return lambda: setattr(GBSTModel, "dump_tree", orig)
    if fault == "fold_skipped":
        # a tree's output reads 0 wherever it is not asked for by `scores`
        # (the fit): the fold leaves z, and z of the test rows, as they were
        orig_out, orig_scores = GBSTModel.tree_output, GBSTModel.scores
        inside = {"scores": 0}

        def scores(self, w, *xargs):
            inside["scores"] += 1
            try:
                return orig_scores(self, w, *xargs)
            finally:
                inside["scores"] -= 1

        def tree_output(self, w, idx, val, gate_mask):
            out = orig_out(self, w, idx, val, gate_mask)
            return out if inside["scores"] else out * 0.0

        GBSTModel.tree_output, GBSTModel.scores = tree_output, scores

        def mend():
            GBSTModel.tree_output, GBSTModel.scores = orig_out, orig_scores

        return mend
    raise ValueError(fault)
