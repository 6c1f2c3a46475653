"""The chip's published peaks and the least work an algorithm needs at a
configuration. Every share of a peak divides a floor time from here by a
measured time. The counting functions (perfbench/work/<name>.py) take the
configuration's sizes alone: no wave log, no counter of the program, no
kernel name. So the share
reads the same work whichever kernel a later PR puts there.
"""

from __future__ import annotations

# per-chip peaks, keyed by jax.devices()[0].device_kind (Google Cloud TPU
# documentation, per-chip figures; copied from bench.py::CHIP_PEAKS). A
# device that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v4": {"bf16": 275e12, "int8": 275e12, "hbm": 1228e9},
    "TPU v5 lite": {"bf16": 197e12, "int8": 393e12, "hbm": 819e9},  # v5e
    "TPU v5": {"bf16": 459e12, "int8": 918e12, "hbm": 2765e9},  # v5p
    "TPU v6 lite": {"bf16": 918e12, "int8": 1836e12, "hbm": 1640e9},  # v6e
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"perfbench: no peak rates for device_kind {device_kind!r}; add "
            f"its published figures to CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})"
        ) from None


def floor_seconds(work: dict, device_kind: str) -> float:
    """Roofline floor: the larger of bytes over the HBM peak and operations
    over the bf16 peak."""
    pk = chip_peaks(device_kind)
    return max(work["bytes"] / pk["hbm"], work["flops"] / pk["bf16"])


def counter(name: str):
    """The work-count function `perfbench/work/<name>.py::count(sizes)`. Each
    returns {"bytes", "flops"} of the least work the algorithm needs for one
    step at a configuration's sizes, with its derivation in its docstring.
    A later PR adds a count by adding a file."""
    from pb.manifest import load_module

    return load_module("work", name).count
