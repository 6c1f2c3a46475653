"""perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on. The
cell's family adapter (perfbench/families/<family>.py) builds the program's
normal trainer, makes the data from the seed, warms up, and hands back a
window of whole steps; this file takes the end-to-end metrics from that
window itself, reads the per-layer metrics through their readers
(perfbench/metrics/<name>.py), has the family's comparison with the plain
reference decide `correct`, and prints the result as the last line of stdout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from pb import manifest, xplane  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".perfbench_work")  # models, traces; git-ignored


def device_info() -> dict:
    """The accelerator as JAX reports it; exits non-zero without one."""
    import jax

    devs = jax.devices()
    if jax.default_backend() != "tpu" or devs[0].platform != "tpu":
        print(f"perfbench: no TPU found (jax backend is "
              f"{jax.default_backend()!r}); there is no CPU mode", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def place_compile_cache() -> str:
    """The program's own rule (ytklearn_tpu/compile_cache.py): the directory
    JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache. Small
    programs are cached too, so a second run compiles nothing."""
    import jax

    from ytklearn_tpu.compile_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class HostProbe:
    """Places a stall. A thread sleeps `tick` seconds at a time over the
    window and keeps the wake-ups that came late by more than `late`: a host
    or a machine that stood still shows there, a device that did not. With
    it the CPU time other guests of the machine took (`steal` of /proc/stat).
    The family's `run.boundaries` say which steps the time was lost in."""

    tick, late = 0.02, 0.1

    def __init__(self):
        import threading

        self.gaps, self.t0, self.steal0 = [], time.perf_counter(), self.steal()
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self.watch, daemon=True)
        self.thread.start()

    @staticmethod
    def steal() -> float:
        try:
            with open("/proc/stat") as f:
                return float(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return 0.0

    def watch(self) -> None:
        last = time.perf_counter()
        while not self.stop.wait(self.tick):
            now = time.perf_counter()
            if now - last - self.tick > self.late:
                self.gaps.append([last - self.t0, now - last - self.tick])
            last = now

    def close(self) -> dict:
        self.stop.set()
        self.thread.join()
        return {"host_late_s": sorted(self.gaps, key=lambda g: -g[1])[:5],
                "steal_s": self.steal() - self.steal0}


class Run:
    """What one run hands to the metric readers and the result line."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool, device: dict):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace_on, self.device = trace, device
        self.work_dir = os.path.join(WORK_DIR, cell.name)
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self.window = None  # pb.window.Window, closed
        self.probe = None  # HostProbe, over the window
        self.setup_s = None
        self.counters_setup = {}  # obs counters at window open
        self.counters_window = {}  # obs counter deltas over the window
        self.gauges = {}
        self.memory_peak_bytes = None
        self.trace = None  # pb.xplane.TraceSummary
        self.facts = {}  # family-specific readings (iterations, ...)
        self.window_counts = {}  # what the window held, where a family counts it
        self.boundaries = []  # (seconds since the window opened, steps) a family saw
        self.checks = {}  # name -> (value, limit)
        self.readings = {}  # every gap the comparison read, compared or not
        self.attempted = self.failed = 0

    # -- called by the family adapter ---------------------------------------
    def open_window(self, steps_done: float, work: float = None, unit: str = "seconds",
                    units_done: float = 0.0) -> None:
        """Set-up is over: snapshot the counters, start the profiler in a
        traced run, and open the window last of all. With `work` the window
        holds that many more `unit`s (`units_done` are done now), whatever
        the clock (pb/window.py)."""
        import jax

        from pb.window import Window
        from ytklearn_tpu import obs

        self.counters_setup = dict(obs.snapshot()["counters"])
        if self.trace_on:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
        self.window = Window(self.seconds, work, unit)
        now = time.perf_counter()
        self.setup_s = now - T_PROCESS
        self.window.open(now, steps_done, units_done)
        self.probe = HostProbe()

    def boundary(self, steps: float) -> None:
        """A family's note of where it stood, for placing a stall; the
        window's own two boundaries are `open_window` and `close_window`."""
        if self.window is not None and self.window.is_open:
            self.boundaries.append(
                [round(time.perf_counter() - self.window.t_open, 4), steps])

    def close_window(self, steps_done: float, exhausted: bool = False,
                     units_done: float = 0.0) -> None:
        """At a boundary with the device drained."""
        import jax

        from ytklearn_tpu import obs

        self.window.close(time.perf_counter(), steps_done, exhausted, units_done)
        self.facts.update(self.probe.close(), boundaries=self.boundaries)
        if self.trace_on:
            jax.profiler.stop_trace()
        snap = obs.snapshot()
        self.counters_window = {
            k: v - self.counters_setup.get(k, 0.0)
            for k, v in snap["counters"].items()
        }
        self.gauges = dict(snap["gauges"])
        self.read_memory()

    def read_memory(self) -> None:
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[: self.cell.chips]
        ]
        self.memory_peak_bytes = max(self.memory_peak_bytes or 0, *peaks)


def end_to_end(run: Run) -> dict:
    """Taken by the benchmark itself from the window, the clock and the
    device's memory statistics; never read from the program."""
    rate = run.cell.config["rate"]
    work = rate["work_per_step"]  # a number, or the name of one of the sizes
    vals = {
        rate["metric"]: run.window.rate(run.cell.sizes[work] if isinstance(work, str) else work),
        "peak_hbm_gib": run.memory_peak_bytes / 2**30,
        "setup_s": run.setup_s,
    }
    out = {}
    for m in run.cell.metrics("end_to_end"):
        if m["name"] not in vals:
            raise SystemExit(f"perfbench: nothing measures {m['name']}")
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out


def per_layer(run: Run) -> dict:
    out = {}
    for m in run.cell.metrics("per_layer"):
        value = manifest.load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def verdict(checks: dict) -> bool:
    """`correct`: every number compared lies at or under its limit. The one
    expression a run, a control and a self-check are judged by."""
    return bool(checks) and all(v <= lim for v, lim in checks.values())


def print_compared(checks: dict) -> dict:
    """Each number compared beside its limit, on standard error."""
    out = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, c in out.items():
        word = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"compared {k}: {c['value']:.6g} limit {c['limit']:.6g} {word}",
              file=sys.stderr)
    return out


def drive(cell, seed: int, seconds: float, trace: bool, device: dict,
          overrides: dict = None, after=None) -> dict:
    """Everything of a run but the look for a chip. `overrides` replaces keys
    of the configuration's `program` block and `after(run, state)` sees the
    run once it is compared: the controls and the self-checks use them; a
    benchmark run passes neither."""
    family = manifest.load_module("families", cell.config["family"])
    run = Run(cell, seed, seconds, trace, device)
    os.makedirs(run.work_dir, exist_ok=True)
    if trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        run.seconds = seconds
    state = family.train(run, overrides or {})
    if run.window is None or run.window.t_close is None:
        raise SystemExit("perfbench: the family handed back no closed window")
    if trace:
        path = xplane.find_xplane(run.trace_dir)
        run.trace = xplane.summarize(path, cell.chips)
        run.facts["xplane_mb"] = os.path.getsize(path) / 1e6  # the profiler keeps ~310
    run.checks = family.compare(run, state)  # reference: after the window
    if after is not None:
        after(run, state)
    result = {
        "correct": verdict(run.checks),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": per_layer(run) if trace else end_to_end(run),
        "device": dict(device, memory_peak_bytes=int(run.memory_peak_bytes)),
    }
    print("perfbench window: " + json.dumps({
        "seconds": run.window.length_s, "steps": run.window.steps,
        "asked_s": seconds, "closed_by": run.window.closed_by,
        "overshoot_s": run.window.overshoot_s,
        "exhausted": run.window.exhausted, "setup_s": run.setup_s,
        "compiles_in_window": run.counters_window.get("compile.traces.backend_compile", 0.0),
        **run.facts,
    }), file=sys.stderr)
    if trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.window.length_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in run.trace.idle_gaps[:10]],
        }
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    if run.window_counts:  # tells two runs of unlike rates whether their work was alike
        result["window"] = run.window_counts
    # each number compared beside its limit: last on stderr, last in the line
    result["compared"] = print_compared(run.checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.benchmark(), args.workload)
    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    device = device_info()
    if device["count"] < cell.chips:
        print(f"perfbench: cell {cell.name} needs {cell.chips} chips, JAX "
              f"found {device['count']}", file=sys.stderr)
        return 2
    from pb.work import chip_peaks

    chip_peaks(device["kind"])  # unknown device: fail before the run
    print(f"perfbench: compile cache {place_compile_cache()}", file=sys.stderr)
    result = drive(cell, args.seed, args.seconds, bool(args.trace), device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
