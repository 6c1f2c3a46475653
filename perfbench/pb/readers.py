"""Arithmetic the per-layer readers share. A reader (perfbench/metrics/
<name>.py) is `read(run) -> float | None`; it returns None where it finds
nothing to read, and the harness then leaves the metric out of the line."""

from __future__ import annotations

from typing import Optional

from pb import work


def counter_in_window(run, name: str) -> float:
    return float(run.counters_window.get(name, 0.0))


def counter_in_setup(run, name: str) -> Optional[float]:
    v = run.counters_setup.get(name)
    return None if v is None else float(v)


def device_ms_per_step(run) -> Optional[float]:
    if run.trace is None or run.window.steps <= 0:
        return None
    return 1e3 * run.trace.busy_s / run.window.steps


def idle_share_pct(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * max(0.0, 1.0 - run.trace.busy_s / run.window.length_s)


def _floor_s(run, work_name: str) -> float:
    return work.floor_seconds(work.counter(work_name)(run.cell.sizes), run.device["kind"])


def step_mfu_pct(run) -> Optional[float]:
    """The whole step's share of the chip's peak: the floor time of the work
    the algorithm needs for the window's steps over the window's seconds."""
    if run.window.steps <= 0:
        return None
    floor = _floor_s(run, run.cell.config["work"]["step"]) * run.window.steps
    return 100.0 * floor / run.window.length_s


def kernel_seconds(run, kernel: str) -> Optional[float]:
    if run.trace is None:
        return None
    return run.trace.op_seconds(run.cell.config["kernels"][kernel])


def kernel_share_pct(run, kernel: str) -> Optional[float]:
    s = kernel_seconds(run, kernel)
    if s is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * s / run.trace.busy_s


def kernel_roofline_pct(run, kernel: str) -> Optional[float]:
    """Floor time of the kernel's work for the window's steps over the device
    seconds its operations took in the trace."""
    s = kernel_seconds(run, kernel)
    if not s or run.window.steps <= 0:
        return None
    floor = _floor_s(run, run.cell.config["work"]["kernels"][kernel])
    return 100.0 * floor * run.window.steps / s
