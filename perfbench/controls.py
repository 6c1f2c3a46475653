"""Readings for the limits of `correct`, taken on the chip at a cell's own
size: the program as the configuration states it (the lower readings), the
lower-precision control the configuration's file names (the upper readings)
and, with --faults, the planted faults. Not part of a benchmark run; one
process reads all seeds, because set-up is long.

    python3 perfbench/controls.py <cell> --seeds 1,2,3 [--seconds 10]
        [--what program,control,faults] [--faults a,b] [--data-seeds 7,8,9]

Every line carries `correct`, decided by the harness's own expression
(`run.verdict`) over the numbers compared and the committed limits: for the
program, a fault or a control that is a path of the program it is the run's
own verdict; for a control that is the reference at a lower precision put in
the program's place, its gaps go through the same expression. With
--data-seeds the i-th seed reads the i-th data seed's rows in place of the
traffic file's (the lower readings should not hang on one set of rows).

One JSON line a run goes to chiprun_out/controls/<cell>.jsonl and to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402
from pb import manifest  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--faults", default="")
    ap.add_argument("--data-seeds", default="")
    args = ap.parse_args()
    cell = manifest.Cell(manifest.benchmark(), args.cell)
    family = manifest.load_module("families", cell.config["family"])
    device = harness.device_info()
    harness.place_compile_cache()
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "controls")
    os.makedirs(out_dir, exist_ok=True)
    control = cell.config["control"]
    with open(os.path.join(out_dir, args.cell + ".jsonl"), "a") as f:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        def one(seed, label, overrides=None, fault=None, reference_control=False):
            """One driven run; with `reference_control` the same run also reads
            the reference at the control's precision in the program's place."""
            got = {}

            def after(run, state):
                got["program"] = dict(run.readings)
                if reference_control:
                    got["control"] = family.control_checks(run, state, control)

            mend = family.plant(fault) if fault else None
            try:
                res = harness.drive(cell, seed, args.seconds, False, device,
                                    overrides=overrides, after=after)
            finally:
                if mend is not None:
                    mend()
            metrics = {k: v["value"] for k, v in res["metrics"].items()}
            base = {"cell": args.cell, "seed": seed,
                    "data_seed": cell.traffic["data_seed"]}
            emit({**base, "what": "program" if reference_control else label,
                  "correct": res["correct"], "readings": got["program"],
                  "compared": res["compared"], "metrics": metrics})
            if reference_control:
                print(f"control {label}, seed {seed}:", file=sys.stderr)
                emit({**base, "what": label,
                      "correct": harness.verdict(got["control"]),
                      "compared": harness.print_compared(got["control"])})

        seeds = [int(s) for s in args.seeds.split(",")]
        data_seeds = [int(s) for s in args.data_seeds.split(",") if s]
        faults = [f for f in args.faults.split(",") if f] or family.FAULTS
        for i, seed in enumerate(seeds):
            if data_seeds:
                cell.traffic["data_seed"] = data_seeds[i % len(data_seeds)]
            for what in args.what.split(","):
                if what == "program":
                    one(seed, "program")
                elif what == "control" and control["kind"] == "program_override":
                    one(seed, "control", overrides=control["overrides"])
                elif what == "control":
                    one(seed, "control", reference_control=True)
                elif what == "faults":
                    for name in faults:
                        one(seed, f"fault:{name}", fault=name)
                else:
                    raise SystemExit(f"unknown --what {what}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
