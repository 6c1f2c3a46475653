"""Record the small trace the self-check of the trace reader reads
(perfbench/selfcheck/data/small.xplane.pb): a jitted loop of a matmul, a
gather and a scatter-add, run three times with a host sleep between, on the
chip. Run once through chiprun; the file comes back under chiprun_out/.
"""

import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from pb import xplane  # noqa: E402


def main() -> int:
    out = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chiprun_out", "small_trace")
    os.makedirs(out, exist_ok=True)
    tmp = os.path.join(out, "trace")

    @jax.jit
    def step(x, idx):
        def body(i, x):
            y = x @ x.T
            g = y[idx]
            return x + 1e-3 * jnp.zeros_like(x).at[idx].add(g @ x)
        return jax.lax.fori_loop(0, 4, body, x)

    x = jnp.ones((512, 256), jnp.float32)
    idx = jnp.arange(512, dtype=jnp.int32)[::-1]
    step(x, idx).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    for i in range(3):
        with jax.profiler.TraceAnnotation("small_step"):
            x = step(x, idx).block_until_ready()
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.02)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = xplane.find_xplane(tmp)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    s = xplane.summarize(path, 1)
    facts = {"window_s": window_s, "busy_s": s.busy_s, "n_events": s.n_events,
             "top_ops": s.top_ops(8), "idle_gaps": s.idle_gaps[:5],
             "device_kind": jax.devices()[0].device_kind,
             "size": os.path.getsize(path)}
    with open(os.path.join(out, "small.facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    with open(os.path.join(out, "describe.txt"), "w") as f:
        f.write(xplane.describe(path, 8))
    shutil.rmtree(tmp)
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
