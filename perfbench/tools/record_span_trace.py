"""Record the small trace the self-check of the span readers reads
(perfbench/selfcheck/data/spans.xplane.pb + spans.facts.json): the program's
own spans as annotations with ids and steps, and a scope map written at
compile, around a jitted step of a scoped gather, its scatter-add and a scoped
matmul, run three times on the chip, each followed by a short sleep under no
step, a small operation and a longer sleep under a span with a step. Run once
through chiprun; the files come back under chiprun_out/span_trace/.

It also answers, for PERF.md, whether a named scope reaches the op-line
events of the installed profiler (`scope_in_event_text`, describe.txt).
"""

import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.dirname(HERE), ROOT):
    sys.path.insert(0, p)
from pb import spans, xplane  # noqa: E402

from ytklearn_tpu import obs  # noqa: E402
from ytklearn_tpu.obs import scopes  # noqa: E402


def main() -> int:
    out = os.path.join(ROOT, "chiprun_out", "span_trace")
    os.makedirs(out, exist_ok=True)
    tmp = os.path.join(out, "trace")
    shutil.rmtree(tmp, ignore_errors=True)
    obs.configure(enabled=True)

    def loss(v, x, idx):
        with scopes.scope("fm.gather_v"):
            g = v[idx]
        with scopes.scope("gbdt.hist"):
            h = (x * jnp.sum(v)) @ x.T
        return jnp.sum(g * g) + 1e-9 * jnp.sum(h * h)

    def small_step(v, x, idx):
        def body(i, v):
            return v - 1e-6 * jax.grad(loss)(v, x, idx)
        return jax.lax.fori_loop(0, 4, body, v)

    @jax.jit
    def tick(v):
        return v[0, 0] + 1.0

    step = scopes.Program(small_step)
    v = jnp.ones((4096, 128), jnp.float32)
    x = jnp.ones((1024, 512), jnp.float32)
    idx = (jnp.arange(65536, dtype=jnp.int32) * 7) % 4096
    with obs.span("small.warm"):
        v = jax.block_until_ready(step(v, x, idx))
        jax.block_until_ready(tick(v))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t_open = time.perf_counter()
    with obs.span("small.run"):
        for i in range(3):
            with obs.step_span("small.step", i):
                v = step(v, x, idx)
                with obs.span("small.wait"):
                    jax.block_until_ready(v)
            time.sleep(0.005)  # idle under no span that carries a step,
            jax.block_until_ready(tick(v))  # ended by a small operation
            with obs.span("small.sleep", step=i):
                time.sleep(0.02)
    t_close = time.perf_counter()
    jax.profiler.stop_trace()

    path = xplane.find_xplane(tmp)
    shutil.copy(path, os.path.join(out, "spans.xplane.pb"))
    summ = xplane.summarize(path, 1)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    reg = obs.spans_between(t_open, t_close)
    ann = spans.trace_annotations(pd)
    offset = spans.clock_offset(ann, reg)
    dev = summ.devices[0]
    gaps = spans.window_gaps(dev.gaps, dev.first_ns, dev.last_ns,
                             t_open + offset, t_close + offset)
    named = spans.name_gaps(gaps, spans.shifted(reg, offset))
    scope_map = scopes.scope_map()
    ops = spans.ops_with_modules(pd)
    scoped = [n for n in ("fm.gather_v", "gbdt.hist")
              if any(n in d for d in dev.op_descr.values())]
    facts = {
        "device_kind": jax.devices()[0].device_kind, "size": os.path.getsize(path),
        "t_open": t_open, "t_close": t_close, "offset": offset,
        "busy_s": summ.busy_s, "n_events": summ.n_events,
        "registry_spans": reg, "n_annotations": len(ann),
        "scope_map": scope_map,
        "scope_seconds": spans.scope_self_seconds(ops, scope_map),
        "idle_s": sum(g["seconds"] for g in named),
        "idle_unnamed_s": sum(g["seconds"] for g in named if g["step"] is None),
        "longest_gaps": sorted(([g["seconds"], g["path"], g["step"]] for g in named),
                               key=lambda g: -g[0])[:6],
        "scope_in_event_text": scoped,
        "modules": sorted({o[2] for o in ops}),
    }
    with open(os.path.join(out, "spans.facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    with open(os.path.join(out, "describe.txt"), "w") as f:
        f.write(xplane.describe(path, 10))
    shutil.rmtree(tmp)
    print(json.dumps({k: v for k, v in facts.items()
                      if k not in ("registry_spans", "scope_map")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
