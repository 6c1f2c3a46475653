"""Family adapter: the field-aware factorization machine through
`HoagTrainer(params, "ffm").train(ingest)`.

Everything of a run but the field of a slot and the reference is the convex
family's (`families/convex.py`, loaded by its path and left as it is): the
rows (`make_rows`), the trainer (`build_trainer`), the recorder around
`minimize_lbfgs`, the window of whole passes, the gaps compared and the
planted faults. Added here: every slot's field, handed to the trainer with
the rows (one feature a field a row: slot 0 is the bias in field 0, as the
program's reader puts it, slot j the j-th column in field j - 1), the field
dictionary's path, and the plain reference `reference/ffm_ref.py`.
"""

from __future__ import annotations

import contextlib
import os

from pb.manifest import ROOT, load_module

convex = load_module("families", "convex")
FAULTS = convex.FAULTS
plant = convex.plant
_build_convex_trainer = convex.build_trainer


@contextlib.contextmanager
def swapped(name: str, value):
    """`convex.<name>` replaced for the length of a call."""
    orig = getattr(convex, name)
    setattr(convex, name, value)
    try:
        yield
    finally:
        setattr(convex, name, orig)


def slot_fields(shape):
    """(rows, width) int32: the field of every slot."""
    import jax.numpy as jnp

    rows, width = shape
    of_slot = jnp.maximum(jnp.arange(width, dtype=jnp.int32) - 1, 0)  # 0, 0, 1, 2, ...
    return jnp.broadcast_to(of_slot, (rows, width))


def check_chunk(params, sizes: dict) -> None:
    """Stops the run at once where the program's FFM, by its own count,
    holds more of a row chunk than the chunk budget: `suggest_chunk` has then
    clamped the chunk at its least size, and a pass at this cell's size
    either does not fit the device or crawls through it (a formulation with
    k alone on the lanes counts 1.6 MB a row: 6.2 GiB a least chunk)."""
    from ytklearn_tpu.config import knobs
    from ytklearn_tpu.models.ffm import FFMModel

    rows, width = int(sizes["train_rows"]), int(sizes["row_width"])
    model = FFMModel(params, int(sizes["hashed_dim"]), n_fields=int(sizes["fields"]))
    chunk = model.suggest_row_chunk(rows, width) or rows
    held = chunk * model.score_bytes_per_row(width)
    budget = knobs.get_int("YTK_CHUNK_BUDGET_MB") << 20
    if held > budget:
        raise SystemExit(
            f"perfbench: this program's FFM counts {held / 2**30:.2f} GiB of "
            f"intermediates in its least row chunk ({chunk} rows), over the "
            f"{budget / 2**30:.2f} GiB chunk budget: it cannot run ffm_criteo")


def build_trainer(run, program: dict):
    """The convex family's trainer, told where the field dictionary lies and
    given the fields with the rows it is handed."""
    trainer = _build_convex_trainer(run, program)
    check_chunk(trainer.params, run.cell.sizes)
    trainer.params.model.field_dict_path = os.path.join(ROOT, program["field_dict"])
    fit = trainer.train

    def train_with_fields(ingest=None):
        for ds in (ingest.train, ingest.test):
            ds.field = slot_fields(ds.idx.shape)
        return fit(ingest=ingest)

    trainer.train = train_with_fields
    return trainer


def train(run, overrides: dict) -> dict:
    with swapped("build_trainer", build_trainer):
        return convex.train(run, overrides)


def reference_run(run, state: dict, compute=None) -> dict:
    """The plain reference from the same start, through the first
    `follow_iterations` iterations."""
    import jax.numpy as jnp
    import numpy as np

    ref = load_module("reference", "ffm_ref")
    cfg, sizes = run.cell.config, run.cell.sizes
    nf, F, k = int(sizes["hashed_dim"]), int(sizes["fields"]), int(sizes["latent_dim"])
    mdl = cfg["model"]
    init = mdl["init"]
    w0 = np.zeros((nf * (1 + F * k),), np.float32)
    rng = np.random.RandomState(int(init["seed"]))
    w0[nf:] = (rng.randn(nf * F * k) * init["std"] + init["mean"]).astype(np.float32)
    if mdl["need_bias"]:
        w0[nf:nf + F * k] = 0.0
    l2 = np.zeros_like(w0)
    l2[1 if mdl["need_bias"] else 0:nf] = mdl["l2"][0]
    l2[nf:] = mdl["l2"][1]
    pass_fn = ref.make_pass(nf, F, k, mdl["need_bias"], mdl["bias_need_latent_factor"],
                            int(cfg["compare"]["reference_block_rows"]),
                            compute=compute or jnp.float32)
    n_iter = min(int(cfg["compare"]["follow_iterations"]), int(state["rec"]["iters"]))
    idx, val, y, wt = state["train"]
    out = ref.follow(pass_fn, w0, (idx, val, slot_fields(idx.shape), y, wt),
                     jnp.asarray(l2), state["g_weight"], n_iter,
                     mdl["line_search"], m=int(sizes["lbfgs_m"]))
    out["w0"] = w0
    return out


def compare(run, state: dict) -> dict:
    """The convex family's gaps (each iteration's loss, the first gradient
    and the weights' change by leaf, what is handed back) against this
    family's reference."""
    with swapped("reference_run", reference_run):
        return convex.compare(run, state)


def control_checks(run, state: dict, control: dict) -> dict:
    with swapped("reference_run", reference_run):
        return convex.control_checks(run, state, control)
