"""Spreads of the runs that tools/sets.sh recorded, by the contract's rule:
the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median, for each
metric in each labelled set. Before them one line a run: its rate beside
what its window held (the result line's `window`, where a family counts it),
so that two runs of unlike rates can be told apart by their work, its length
and what closed it.

    python3 perfbench/tools/spread.py chiprun_out/sets/<cell>.jsonl
"""

import collections
import json
import statistics
import sys


def main() -> int:
    sets = collections.defaultdict(lambda: collections.defaultdict(list))
    bad = 0
    for line in open(sys.argv[1]):
        rec = json.loads(line)
        res = rec.get("result")
        if not res or rec["trace"]:
            continue
        bad += 0 if res["correct"] else 1
        rates = [v["value"] for k, v in res["metrics"].items() if "_per_s" in k]
        win = rec.get("window") or {}
        print(f"{rec['label']:12s} seed {rec['seed']} rate {rates[0] if rates else None} "
              f"correct {res['correct']} failed {res['failed']} of {res['attempted']} "
              f"window {json.dumps(res.get('window'))} {win.get('seconds')} s "
              f"closed by {win.get('closed_by')}")
        for k, v in res["metrics"].items():
            sets[rec["label"]][k].append(v["value"])
        sets[rec["label"]]["_wall_s"].append(rec["wall_s"])
    for label, metrics in sets.items():
        for k, vals in metrics.items():
            if len(vals) < 2:
                print(f"{label:12s} {k:16s} n={len(vals)} {vals}")
                continue
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            print(f"{label:12s} {k:16s} n={len(vals)} median={med:.6g} "
                  f"iqr/median={(q[2] - q[0]) / med:.5f} min={min(vals):.6g} max={max(vals):.6g}")
    print(f"runs with correct false: {bad}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
