"""A look at a trace by hand: one traced run of a cell whose xplane file is
kept, described (planes, lines, first events, all distinct op descriptions)
under chiprun_out/. Not part of a benchmark run.

    python3 perfbench/tools/trace_look.py <cell> <seed> [seconds]
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import run  # noqa: E402
from pb import manifest, xplane  # noqa: E402


def main() -> int:
    name, seed = sys.argv[1], int(sys.argv[2])
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 8.0
    out_dir = os.path.join(run.ROOT, "chiprun_out", "trace_look", name)
    os.makedirs(out_dir, exist_ok=True)
    cell = manifest.Cell(manifest.benchmark(), name)
    device = run.device_info()
    run.place_compile_cache()
    real_summarize = xplane.summarize

    def keep_trace(path, chips=1):
        shutil.copy(path, os.path.join(out_dir, "kept.xplane.pb"))
        return real_summarize(path, chips)

    run.xplane.summarize = keep_trace
    result = run.drive(cell, seed, seconds, True, device)
    path = os.path.join(out_dir, "kept.xplane.pb")
    with open(os.path.join(out_dir, "describe.txt"), "w") as f:
        f.write(xplane.describe(path, max_events=12))
    summ = real_summarize(path, cell.chips)
    with open(os.path.join(out_dir, "ops.json"), "w") as f:
        json.dump({"ops": sorted(
            ((k, s, summ.devices[0].op_descr[k][:1500])
             for k, s in summ.devices[0].op_self_s.items()),
            key=lambda r: -r[1])[:80], "idle": summ.idle_gaps[:30],
            "n_events": summ.n_events, "size": os.path.getsize(path)}, f, indent=1)
    if os.path.getsize(path) > 20 << 20:
        os.unlink(path)  # too large to bring back
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
