"""Tiny cells for the CPU self-checks: the committed configuration with the
sizes cut, handed to `run.drive` in place of a cell of BENCHMARK.json."""

from __future__ import annotations

import copy
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from pb import manifest  # noqa: E402

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}  # peaks only


def tiny_cell(name: str, sizes: dict, program: dict = None, traffic: dict = None):
    cell = manifest.Cell(manifest.benchmark(), name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = {**cell.traffic, **(traffic or {})}
    cell.config["program"].update(program or {})
    cell.sizes = {**cell.sizes, **sizes}
    return cell
