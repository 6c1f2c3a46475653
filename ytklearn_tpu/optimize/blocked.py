"""Blocked (row-chunked) loss / gradient / score evaluation.

The reference never materializes per-sample intermediates for a whole
partition at once: CoreData is deliberately *blocked* storage
(MAX_2D_LEN=50000 / MAX_1D_LEN=2e6 caps, reference dataflow/CoreData.java:51-52)
and every convex optimizer walks blocks in its loss loop (e.g. reference
optimizer/FMHoagOptimizer.java:88). The TPU equivalent implemented here:
evaluate loss+grad as a `lax.scan` over fixed-size row chunks — loss and
gradient are row sums, so the scan accumulates both with peak memory
O(chunk x per-row cost) instead of O(n x per-row cost). This is what lets
FM/FFM train full-batch L-BFGS on data whose per-row score intermediates
(latent gathers) would otherwise exceed HBM.

On a device mesh the scan runs per-shard inside `shard_map` with a final
psum — the same collective XLA inserts for the unchunked row-sharded
program, so chunked and unchunked mesh evaluation are interchangeable.

Batch elements that are NOT row-aligned (e.g. the GBST per-feature gate
mask) are threaded through unchunked via `row_mask`.

Once a pass, once a chunk. A chunk's function is `fn(w, *chunk)`, and by
default all of it runs once a chunk. A caller may hand the same function in
two parts, `split = (prepare, fn_p)` with `fn(w, *chunk) == fn_p(prepare(w),
*chunk)` (a model declares them: models/base.py `ConvexModel.prepare`).
Then what depends on the parameters alone runs once a pass, outside the
scan, under the scope `blocked.prepare`:

    p, pull = jax.vjp(prepare, w)        once a pass
    scan:  l, gp += value_and_grad(fn_p)(p, *chunk)   once a chunk, the
           gradient summed in p's layout
    (g,) = pull(gp)                      once a pass: the gradient turned
                                         back into w's layout

`chunked_sum` and `blocked_rows` call `prepare` once before their scan.
The sum over chunks is the same sum in the same order, element by element;
what prepare's transpose does to a gradient (a mask's zeroing, a concat's
split) is linear and applied to the sum instead of to every term. Without a
split the scan's body is `value_and_grad(fn)(w, *chunk)` as it always was:
which of the two is traced follows from whether a split was handed in,
nothing else. The unchunked path (`chunk is None`) never looks at it.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.scopes import scope

#: `(prepare, fn_p)`: `prepare(w) -> p` once a pass, `fn_p(p, *chunk)` once
#: a chunk (see the module text)
Split = Tuple[Callable, Callable]


def _split_rows(batch, row_mask):
    rows = tuple(a for a, r in zip(batch, row_mask) if r)
    consts = tuple(a for a, r in zip(batch, row_mask) if not r)
    return rows, consts


def _rebuild(row_mask, rows, consts):
    ri, ci = iter(rows), iter(consts)
    return tuple(next(ri) if r else next(ci) for r in row_mask)


def _stack_chunks(rows, chunk: int):
    """Pad row arrays to a multiple of `chunk` and reshape to
    (n_chunks, chunk, ...). Padding rows are all-zero — ingest already pads
    with zero-weight rows, and every model loss masks weight==0 rows, so
    padded rows contribute exactly 0 to loss and gradient.

    A chunk is NEVER padded beyond the data: chunking exists to cap memory
    on large n, not to tax small n (reference contract: blocks cap memory,
    optimizer/FMHoagOptimizer.java:88). Under shard_map n is the SHARD's
    row count, so a small per-shard slice of a big batch — the r5
    eval-amplification bug, ~20x compute per line-search trial on the
    8-device test mesh — collapses to one exact-size chunk here."""
    n = rows[0].shape[0]
    chunk = min(chunk, n)
    nc = -(-n // chunk)
    pad = nc * chunk - n

    def prep(a):
        if pad:
            a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape((nc, chunk) + a.shape[1:])

    return tuple(prep(a) for a in rows), n


def chunked_value_and_grad(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]] = None,
    vary_axes: Tuple[str, ...] = (),
    split: Optional[Split] = None,
) -> Callable:
    """(w, *batch) -> (sum loss, sum grad), scanning row chunks.

    `fn(w, *batch)` must return a weighted-sum (not averaged) scalar loss —
    the same contract `minimize_lbfgs` imposes — so chunk sums compose.
    `vary_axes`: mesh axes this runs under inside shard_map. `w` is made
    explicitly varying over them so the computed gradient stays the
    *per-shard local* grad (AD would otherwise transpose the implicit
    pvary of replicated w into a psum, and the caller's own psum would
    then double-count) — the caller psums loss and grad exactly once.
    `split`: `fn` in two parts; the gradient is then summed in the layout
    of `prepare(w)` and turned back once, after the scan.
    """

    def run(w, *batch):
        mask = tuple(row_mask) if row_mask is not None else (True,) * len(batch)
        rows, consts = _split_rows(batch, mask)
        xs, _ = _stack_chunks(rows, chunk)
        if vary_axes:
            w = lax.pcast(w, vary_axes, to="varying")
        if split is None:
            p, per_chunk = w, fn
        else:
            prepare, per_chunk = split
            with scope("blocked.prepare"):
                p, pull = jax.vjp(prepare, w)

        def body(carry, ch):
            l, g = jax.value_and_grad(per_chunk)(p, *_rebuild(mask, ch, consts))
            if split is not None:
                # a chunk's gradient is summed by itself, then added to the
                # carry once, as it is where the transposes of `prepare`
                # stand between the two. Left to itself XLA makes the carry
                # the scatter-add's operand: every update of a row then
                # meets the running sum of the whole pass one by one, and a
                # hot id's millions of float32 updates lose what summing
                # each chunk's among themselves first keeps (on the chip:
                # 40 times the gap to a float32 reference and FM's check
                # failed, PERF.md PR 32)
                g = lax.optimization_barrier(g)
            return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

        init = (jnp.zeros((), w.dtype), jax.tree.map(jnp.zeros_like, p))
        if vary_axes:
            init = (lax.pcast(init[0], vary_axes, to="varying"), init[1])
        (loss, grad), _ = lax.scan(body, init, xs)
        if split is not None:
            with scope("blocked.prepare"):
                (grad,) = pull(grad)
        return loss, grad

    return run


def _prepared(fn: Callable, split: Optional[Split], w):
    """(p, per_chunk) of a forward-only scan: `prepare(w)` made here, once,
    where a split is given."""
    if split is None:
        return w, fn
    prepare, per_chunk = split
    with scope("blocked.prepare"):
        return prepare(w), per_chunk


def chunked_sum(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]] = None,
    vary_axes: Tuple[str, ...] = (),
    split: Optional[Split] = None,
) -> Callable:
    """(w, *batch) -> sum loss only (no gradient) — the cheap evaluation
    path (per-iteration test loss, round selection)."""

    def run(w, *batch):
        mask = tuple(row_mask) if row_mask is not None else (True,) * len(batch)
        rows, consts = _split_rows(batch, mask)
        xs, _ = _stack_chunks(rows, chunk)
        p, per_chunk = _prepared(fn, split, w)

        def body(carry, ch):
            return carry + per_chunk(p, *_rebuild(mask, ch, consts)), None

        init = jnp.zeros(())
        if vary_axes:
            init = lax.pcast(init, vary_axes, to="varying")
        loss, _ = lax.scan(body, init, xs)
        return loss

    return run


def blocked_rows(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]] = None,
    split: Optional[Split] = None,
) -> Callable:
    """Chunked per-row outputs: fn(w, *batch) -> (n, ...) evaluated as
    `lax.map` over row chunks, concatenated and sliced back to n rows.
    Used for scores/predicts on batches whose per-row intermediates don't
    fit at once (reference analog: OnlinePredictor scoring block-by-block
    over CoreData blocks)."""

    def run(w, *batch):
        mask = tuple(row_mask) if row_mask is not None else (True,) * len(batch)
        rows, consts = _split_rows(batch, mask)
        xs, n = _stack_chunks(rows, chunk)
        p, per_chunk = _prepared(fn, split, w)
        out = lax.map(lambda ch: per_chunk(p, *_rebuild(mask, ch, consts)), xs)
        return out.reshape((-1,) + out.shape[2:])[:n]

    return run


def mesh_chunked_value_and_grad(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]],
    mesh,
    axis: str,
    n_batch: int,
    split: Optional[Split] = None,
) -> Callable:
    """`chunked_value_and_grad` run per-shard under shard_map with a final
    psum over the data axis — the reference's grad allreduce
    (optimizer/HoagOptimizer.java:1038) with the block loop inside each
    rank, matching its per-thread CoreData block walk. With a `split` every
    shard prepares its own copy and turns its local gradient back before
    the psum, which stays one collective a pass on w's layout."""
    mask = tuple(row_mask) if row_mask is not None else (True,) * n_batch
    cvg = chunked_value_and_grad(fn, chunk, mask, vary_axes=(axis,), split=split)
    in_specs = (P(), tuple(P(axis) if r else P() for r in mask))
    out_specs = (P(), P())

    from ..parallel.collectives import psum

    def local(w, batch):
        loss, grad = cvg(w, *batch)
        return psum(loss, axis), psum(grad, axis)

    sm = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return lambda w, *batch: sm(w, batch)


def mesh_chunked_sum(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]],
    mesh,
    axis: str,
    n_batch: int,
    split: Optional[Split] = None,
) -> Callable:
    """`chunked_sum` per shard under shard_map + psum. Reshaping a
    row-sharded global array for the plain scan would make XLA all-gather
    the batch onto every device — this keeps each shard's chunks local."""
    mask = tuple(row_mask) if row_mask is not None else (True,) * n_batch
    cs = chunked_sum(fn, chunk, mask, vary_axes=(axis,), split=split)
    in_specs = (P(), tuple(P(axis) if r else P() for r in mask))

    from ..parallel.collectives import psum

    def local(w, batch):
        return psum(cs(w, *batch), axis)

    sm = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P())
    return lambda w, *batch: sm(w, batch)


def mesh_blocked_rows(
    fn: Callable,
    chunk: int,
    row_mask: Optional[Sequence[bool]],
    mesh,
    axis: str,
    n_batch: int,
    split: Optional[Split] = None,
) -> Callable:
    """`blocked_rows` per shard under shard_map — per-row outputs stay
    row-sharded (out_specs P(axis)), no collective needed."""
    mask = tuple(row_mask) if row_mask is not None else (True,) * n_batch
    br = blocked_rows(fn, chunk, mask, split=split)
    in_specs = (P(), tuple(P(axis) if r else P() for r in mask))

    def local(w, batch):
        return br(w, *batch)

    sm = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(axis))
    return lambda w, *batch: sm(w, batch)


# -- dispatch factories: one place for the (unchunked | chunked | mesh-
# chunked) selection so every call site (lbfgs programs, trainer eval
# paths, HOAG test gradient) stays in sync. `split` is `fn` in two parts
# (module text); the unchunked path evaluates `fn` whole -------------------


def make_value_and_grad(
    fn, chunk=None, row_mask=None, mesh=None, axis="data", n_batch=0, split=None
):
    if chunk is None:
        return jax.value_and_grad(fn)
    if mesh is None:
        return chunked_value_and_grad(fn, chunk, row_mask, split=split)
    return mesh_chunked_value_and_grad(
        fn, chunk, row_mask, mesh, axis, n_batch, split=split
    )


def make_sum(
    fn, chunk=None, row_mask=None, mesh=None, axis="data", n_batch=0, split=None
):
    if chunk is None:
        return fn
    if mesh is None:
        return chunked_sum(fn, chunk, row_mask, split=split)
    return mesh_chunked_sum(fn, chunk, row_mask, mesh, axis, n_batch, split=split)


def make_rows(
    fn, chunk=None, row_mask=None, mesh=None, axis="data", n_batch=0, split=None
):
    if chunk is None:
        return fn
    if mesh is None:
        return blocked_rows(fn, chunk, row_mask, split=split)
    return mesh_blocked_rows(fn, chunk, row_mask, mesh, axis, n_batch, split=split)


def pow2_floor(x: int) -> int:
    return 1 << max(int(x).bit_length() - 1, 0)


def suggest_chunk(
    n_rows: int,
    bytes_per_row: int,
    budget_bytes: Optional[int] = None,
    min_chunk: int = 4096,
    n_shards: int = 1,
) -> Optional[int]:
    """Pick a power-of-two row chunk so the score intermediates stay under
    `budget_bytes` (default 1 GiB, env YTK_CHUNK_BUDGET_MB). Returns None
    when the whole batch already fits (no chunking needed).

    All decisions are made on the PER-SHARD row count (`n_rows` is the
    global batch; on a mesh each shard scans its own rows): a shard at or
    under `min_chunk` rows never chunks — chunking exists to cap memory on
    large n, never to tax small n. The r5 regression this guards against:
    FFM's padded per-row estimate forced chunking at ~1.6k global rows,
    and each 200-row test-mesh shard was padded to a 4096-row chunk —
    ~20x compute amplification per line-search trial (test_ffm_agaricus
    3088 s). Now: local_rows <= min_chunk -> None."""
    from ..config import knobs

    local_rows = -(-n_rows // max(n_shards, 1))
    if budget_bytes is None:
        budget_bytes = knobs.get_int("YTK_CHUNK_BUDGET_MB") << 20
    env = knobs.get_int("YTK_ROW_CHUNK")
    if env is not None:
        chunk = env
        return chunk if 0 < chunk < local_rows else None
    if local_rows <= min_chunk:
        return None
    if local_rows * bytes_per_row <= budget_bytes:
        return None
    chunk = max(min_chunk, pow2_floor(budget_bytes // max(bytes_per_row, 1)))
    return chunk if chunk < local_rows else None
