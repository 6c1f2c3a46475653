"""Bench regression gate: compare the newest two BENCH_*.json artifacts.

The checked-in BENCH_r*.json trajectory was archaeology — numbers you
could read but nothing watched. This gate turns it into a signal: the
newest comparable pair must not regress on

  headline throughput   new value >= old * (1 - tol)   (tol default 15%)
  downgrades            AOT compile-probe fallbacks must not increase
  health events         sentinel hits (health.*) must not increase

Comparable = both artifacts parse to a bench record (the CI driver
wrapper's "parsed" block or a raw bench line) AND report the same
"metric" — a linear-era artifact is never compared against a GBDT one.

Serve gate: SERVE_r*.json artifacts (scripts/serve_bench.py --record;
schema "serve_latency", or "serve_rungs" whose artifact carries one
record PER scoring rung) are compared on the latency axes that matter
for serving — but ONLY between records with the same metric AND the same
rung identity (fused, binned, precision) AND the same recorded host
core count (`cpu_count`, absent on older artifacts): a binned-rung
number vs a default-path number is an uplift, not a regression signal,
exactly like the fleet gate's same-replica-count rule — and a 1-core
container's req/s vs an 8-core box's is a hardware delta, not a code
one. Pre-rung artifacts count as the default rung, so the schema bump
never breaks the gate; downgraded rung runs (a Mosaic fallback measured
on its fallback path) skip. The absolute gates below still apply to the
newest artifact no matter what it pairs with.

  sustained req/s       new >= old * (1 - tol)
  p99 latency           new <= old * (1 + tol)   (the latency band)
  retraces_after_warmup must stay 0

Rung quality gate: the newest serve_rungs artifact's recorded quality
bands are re-checked absolutely — binned request-stream band under
SERVE_BINNED_BAND, every bf16 family band under SERVE_BF16_BAND — so a
relaxed-precision rung can never quietly ship outside its envelope.

Tracing-overhead gate: the newest serve_rungs artifact's recorded
tracing_overhead line is re-checked absolutely — 1%-head-sampled request
tracing must stay within the throughput band of tracing-off. Artifacts
predating the field skip cleanly.

Quality-overhead gate: same shape for the model-quality plane's
quality_overhead line (obs/quality.py row sampler at its default
YTK_QUALITY_SAMPLE vs off); artifacts predating the field skip cleanly.

Transform-overhead gate: the newest serve_rungs artifact's recorded
transform_overhead line (ISSUE 19, docs/transform.md) is re-checked
absolutely — the raw-feature-dict wire path must be bit-identical to
pre-assembled vectors and hold zero steady-state retraces; artifacts
predating the field skip cleanly.

Fleet gate: schema "serve_fleet" artifacts (schema_version 2,
`serve_bench.py --fleet`) are a different workload — N replica processes
— so they are compared ONLY against predecessors with the same metric
AND the same replica count (a 4-replica number vs a 2-replica number is
not a regression signal), on req/s floor, p99 ceiling, and zero
fleet-wide retraces. Single-process serve artifacts skip fleet records
cleanly (and vice versa), so the schema bump never breaks the gate.

Ramp gate: SCALE_r*.json artifacts (`serve_bench.py --ramp`, schema
"serve_scale") carry the autoscaler elasticity story. The NEWEST one is
re-gated absolutely (zero request failures, shrink back to the floor,
sheds confined to the pre-scale window), and when a predecessor with the
same (metric, replicas_min, replicas_max) band exists, the peak replica
count reached under the same ramp must not regress. Skips cleanly when
no serve_scale artifact exists.

GOSS gate: the newest ABLATION_r*.json holding both a `goss` arm and a
both-off baseline arm (`part`, else `b256`/`nopart`) is checked WITHIN
the artifact — the headline ships with GOSS on, so a previous-BENCH
comparison alone can't see a change that silently degrades the sampling
win or its quality:

  goss win-rate    goss trees/s >= baseline trees/s * GOSS_MIN_SPEEDUP
                   (default 1.0 — sampling must never LOSE throughput)
  goss quality     auc(goss) >= auc(baseline) - GOSS_AUC_TOL (0.005;
                   one-sided — only a quality loss trips)

Exit 0 with a skip message when fewer than two comparable artifacts exist
(fresh clones pass — and so do clones that have only training BENCH
artifacts and no serve ones, or no ablation artifact with goss arms),
exit 1 with the offending axis on regression.

Usage: scripts/check_bench_regress.py [--dir REPO] [--tol 0.15]
Wired into the verify recipe next to check_no_print.sh /
check_suite_time.sh (ROADMAP.md).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ablate_engine import read_bench_record  # noqa: E402


def find_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted by round number (BENCH_r<NN>.json)."""
    out = []
    for path in glob.glob(os.path.join(repo, "BENCH_*.json")):
        m = re.search(r"BENCH_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def comparable_pair(artifacts: List[Tuple[int, str]]):
    """Newest two records sharing a metric, or None. Unparseable / rc!=0
    rounds (parsed: null) are skipped, not fatal."""
    usable = []
    for rnd, path in artifacts:
        try:
            rec = read_bench_record(path)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if rec.get("metric") and rec.get("trees_per_sec") is not None:
            usable.append((rnd, path, rec))
        else:
            print(f"  [skip] {os.path.basename(path)}: no parsed bench line")
    if len(usable) < 2:
        return None
    newest = usable[-1]
    for older in reversed(usable[:-1]):
        if older[2]["metric"] == newest[2]["metric"]:
            return older, newest
    return None


def check(old, new, tol: float) -> List[str]:
    """-> list of failure messages (empty = gate passes)."""
    (o_rnd, o_path, o), (n_rnd, n_path, n) = old, new
    fails = []
    floor = o["trees_per_sec"] * (1.0 - tol)
    print(
        f"  throughput: r{n_rnd} {n['trees_per_sec']:.3f} vs r{o_rnd} "
        f"{o['trees_per_sec']:.3f} (floor {floor:.3f}, tol {tol:.0%})"
    )
    if n["trees_per_sec"] < floor:
        fails.append(
            f"throughput regressed: {n['trees_per_sec']:.3f} < "
            f"{o['trees_per_sec']:.3f} * (1 - {tol}) = {floor:.3f}"
        )
    print(f"  downgrades: r{n_rnd} {n['downgrades']} vs r{o_rnd} {o['downgrades']}")
    if n["downgrades"] > o["downgrades"]:
        fails.append(
            f"downgrades increased: {o['downgrades']} -> {n['downgrades']} "
            "(a kernel rung was lost — see the artifact's obs counters)"
        )
    print(
        f"  health events: r{n_rnd} {n['health_events']} vs "
        f"r{o_rnd} {o['health_events']}"
    )
    if n["health_events"] > o["health_events"]:
        fails.append(
            f"health sentinel hits increased: {o['health_events']} -> "
            f"{n['health_events']} (see health.* counters / flight dump)"
        )
    return fails


# ---------------------------------------------------------------------------
# Serve (latency-schema) artifacts
# ---------------------------------------------------------------------------


def find_serve_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted by round number (SERVE_r<NN>.json)."""
    out = []
    for path in glob.glob(os.path.join(repo, "SERVE_*.json")):
        m = re.search(r"SERVE_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


DEFAULT_RUNG = {"fused": False, "binned": False, "precision": "f64"}


def _rung_of(rec: dict) -> tuple:
    """(fused, binned, precision) identity — pre-rung artifacts ran the
    default path, so missing fields mean the default rung."""
    return (
        bool(rec.get("fused", False)),
        bool(rec.get("binned", False)),
        str(rec.get("precision", "f64")),
    )


def read_serve_records(path: str) -> List[dict]:
    """Normalized single-process serve records from one artifact (raw or
    CI-driver-wrapped): a serve_latency artifact yields one default-rung
    record; a serve_rungs artifact yields one record PER rung. Records
    are only comparable at the same (metric, rung) — the r14
    same-replica-count rule applied to the precision/fused axis."""
    import json

    with open(path) as f:
        rec = json.load(f)
    if "parsed" in rec and "cmd" in rec:  # CI driver wrapper
        rec = rec["parsed"] or {}
    if rec.get("schema") == "serve_latency":
        return [{
            "metric": rec.get("metric"),
            "rung": _rung_of({}),
            "label": "default",
            "req_per_sec": rec.get("value"),
            "p99_ms": rec.get("p99_ms"),
            "retraces": rec.get("retraces_after_warmup"),
            "raw": rec,
        }]
    if rec.get("schema") == "serve_rungs":
        out = []
        for entry in rec.get("rungs") or []:
            out.append({
                "metric": rec.get("metric"),
                "rung": _rung_of(entry),
                "label": entry.get("rung"),
                "req_per_sec": entry.get("req_per_sec"),
                "p99_ms": entry.get("p99_ms"),
                "retraces": entry.get("retraces_after_warmup"),
                "downgraded": entry.get("downgraded", False),
                "cpus": rec.get("cpu_count"),
                "raw": rec,
            })
        return out
    return []


def serve_comparable_pairs(artifacts: List[Tuple[int, str]]):
    """[(old, new)] — for EVERY rung record in the newest serve artifact,
    the nearest older record with the same (metric, rung, host core
    count). Rungs with no same-rung predecessor (first artifact after a
    rung ships, a downgraded rung measured as its fallback, or no
    predecessor recorded on same-size hardware — a 1-core container's
    req/s vs an 8-core box's is not a regression signal) skip cleanly;
    the absolute gates (quality bands, overhead lines, retraces) still
    apply to the newest artifact regardless."""
    per_artifact = []
    for rnd, path in artifacts:
        try:
            recs = [
                r for r in read_serve_records(path)
                if r.get("metric") and r.get("req_per_sec") is not None
            ]
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if recs:
            per_artifact.append((rnd, path, recs))
        else:
            print(f"  [skip] {os.path.basename(path)}: no serve records")
    if len(per_artifact) < 2:
        return []
    n_rnd, n_path, newest = per_artifact[-1]
    pairs = []
    for rec in newest:
        if rec.get("downgraded"):
            # a downgraded rung ran its FALLBACK path; its number is not
            # this rung's signal (the fallback is gated via its own rung)
            print(
                f"  [skip] r{n_rnd} rung {rec['label']}: downgraded run"
            )
            continue
        for o_rnd, o_path, older in reversed(per_artifact[:-1]):
            match = next(
                (o for o in older
                 if o["metric"] == rec["metric"]
                 and o["rung"] == rec["rung"]
                 and o.get("cpus") == rec.get("cpus")
                 and not o.get("downgraded")),
                None,
            )
            if match is not None:
                pairs.append(
                    ((o_rnd, o_path, match), (n_rnd, n_path, rec))
                )
                break
        else:
            rung_only = any(
                o["metric"] == rec["metric"] and o["rung"] == rec["rung"]
                and not o.get("downgraded")
                for _, _, older in per_artifact[:-1] for o in older
            )
            why = ("recorded on different hardware (core count)"
                   if rung_only else "no same-rung predecessor")
            print(f"  [skip] r{n_rnd} rung {rec['label']}: {why}")
    return pairs


def read_fleet_records(path: str) -> List[dict]:
    """Normalized fleet records: a serve_fleet artifact (legacy, default
    rung), or the fleet run embedded in a serve_rungs artifact (rung
    fields carried). [] for anything else."""
    import json

    with open(path) as f:
        rec = json.load(f)
    if "parsed" in rec and "cmd" in rec:  # CI driver wrapper
        rec = rec["parsed"] or {}
    if rec.get("schema") == "serve_fleet":
        return [{
            "metric": rec.get("metric"),
            "rung": _rung_of({}),
            "replicas": rec.get("replicas"),
            "req_per_sec": rec.get("value"),
            "p99_ms": rec.get("p99_ms"),
            "retraces": rec.get("retraces_fleet"),
            "raw": rec,
        }]
    if rec.get("schema") == "serve_rungs" and rec.get("fleet"):
        f_rec = rec["fleet"]
        return [{
            "metric": f_rec.get("metric"),
            "rung": _rung_of(f_rec),
            "replicas": f_rec.get("replicas"),
            "req_per_sec": f_rec.get("req_per_sec"),
            "p99_ms": f_rec.get("p99_ms"),
            "retraces": f_rec.get("retraces_fleet"),
            "raw": rec,
        }]
    return []


def fleet_comparable_pair(artifacts: List[Tuple[int, str]]):
    """Newest two fleet records sharing (metric, replica count, rung) — a
    fleet number is only comparable at the same fan-out AND the same
    scoring rung (a binned fleet vs a default fleet is an uplift, not a
    regression signal)."""
    usable = []
    for rnd, path in artifacts:
        try:
            recs = read_fleet_records(path)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        for rec in recs:
            if rec.get("metric") and rec.get("req_per_sec") is not None:
                usable.append((rnd, path, rec))
    if len(usable) < 2:
        return None
    newest = usable[-1]
    for older in reversed(usable[:-1]):
        if (older[2]["metric"] == newest[2]["metric"]
                and older[2]["replicas"] == newest[2]["replicas"]
                and older[2]["rung"] == newest[2]["rung"]):
            return older, newest
    return None


def check_rung_quality(artifacts: List[Tuple[int, str]]) -> List[str]:
    """Absolute quality-band gate on the NEWEST serve_rungs artifact:
    the binned rung's request-stream band and every bf16 family band must
    stay inside the same envelopes serve_bench enforces at record time
    (env SERVE_BINNED_BAND / SERVE_BF16_BAND)."""
    import json

    binned_band = float(os.environ.get("SERVE_BINNED_BAND", "1e-9"))
    bf16_band = float(os.environ.get("SERVE_BF16_BAND", "0.1"))
    for rnd, path in reversed(artifacts):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in rec and "cmd" in rec:
            rec = rec["parsed"] or {}
        if rec.get("schema") != "serve_rungs":
            continue
        fails = []
        quality = rec.get("binned_quality") or {}
        band = quality.get("max_abs_pred_diff")
        if band is not None:
            print(f"  rung quality (r{rnd}): binned stream band {band:.3g} "
                  f"(limit {binned_band:.3g})")
            if band > binned_band:
                fails.append(
                    f"binned rung quality band {band:.3g} > "
                    f"{binned_band:.3g} in {os.path.basename(path)} "
                    "(env SERVE_BINNED_BAND)"
                )
        for family, b in sorted((rec.get("precision_bands") or {}).items()):
            print(f"  rung quality (r{rnd}): bf16 {family} band {b:.3g} "
                  f"(limit {bf16_band:.3g})")
            if b > bf16_band:
                fails.append(
                    f"bf16 band {b:.3g} > {bf16_band:.3g} for {family} in "
                    f"{os.path.basename(path)} (env SERVE_BF16_BAND)"
                )
        return fails
    print("  rung quality: no serve_rungs artifact (skip)")
    return []


def check_tracing_overhead(
    artifacts: List[Tuple[int, str]], tol: float
) -> List[str]:
    """Absolute gate on the NEWEST serve_rungs artifact's recorded
    tracing-overhead line: 1%-sampled request tracing must stay within
    the regress band of tracing-off. Artifacts predating the field (and
    non-rung schemas) skip cleanly."""
    import json

    for rnd, path in reversed(artifacts):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in rec and "cmd" in rec:
            rec = rec["parsed"] or {}
        if rec.get("schema") != "serve_rungs":
            continue
        t = rec.get("tracing_overhead") or {}
        off = t.get("off_req_per_sec")
        sampled = t.get("sampled_req_per_sec")
        if not off or sampled is None:
            print(f"  tracing overhead: r{rnd} predates the field (skip)")
            return []
        floor = off * (1.0 - tol)
        print(
            f"  tracing overhead (r{rnd}): sampled {sampled:.1f} vs off "
            f"{off:.1f} req/s (floor {floor:.1f}, tol {tol:.0%})"
        )
        if sampled < floor:
            return [
                f"sampled tracing overhead out of band: {sampled:.1f} < "
                f"{off:.1f} * (1 - {tol}) req/s in "
                f"{os.path.basename(path)}"
            ]
        return []
    print("  tracing overhead: no serve_rungs artifact (skip)")
    return []


def check_quality_overhead(
    artifacts: List[Tuple[int, str]], tol: float
) -> List[str]:
    """Absolute gate on the NEWEST serve_rungs artifact's recorded
    quality-overhead line (ISSUE 15): the model-quality row sampler at
    its default rate must stay within the regress band of quality-off.
    Artifacts predating the field (r17 and older) skip cleanly."""
    import json

    for rnd, path in reversed(artifacts):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in rec and "cmd" in rec:
            rec = rec["parsed"] or {}
        if rec.get("schema") != "serve_rungs":
            continue
        q = rec.get("quality_overhead") or {}
        off = q.get("off_req_per_sec")
        sampled = q.get("sampled_req_per_sec")
        if not off or sampled is None:
            print(f"  quality overhead: r{rnd} predates the field (skip)")
            return []
        floor = off * (1.0 - tol)
        print(
            f"  quality overhead (r{rnd}): sampled {sampled:.1f} vs off "
            f"{off:.1f} req/s (floor {floor:.1f}, tol {tol:.0%})"
        )
        if sampled < floor:
            return [
                f"quality-sampler overhead out of band: {sampled:.1f} < "
                f"{off:.1f} * (1 - {tol}) req/s in "
                f"{os.path.basename(path)}"
            ]
        return []
    print("  quality overhead: no serve_rungs artifact (skip)")
    return []


def check_transform_overhead(
    artifacts: List[Tuple[int, str]]
) -> List[str]:
    """Absolute gate on the NEWEST serve_rungs artifact's recorded
    transform-overhead line (ISSUE 19): the raw-feature-dict wire path
    must score bit-identically to pre-assembled vectors and hold zero
    steady-state retraces. Artifacts predating the field (r21 and
    older) skip cleanly."""
    import json

    for rnd, path in reversed(artifacts):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if "parsed" in rec and "cmd" in rec:
            rec = rec["parsed"] or {}
        if rec.get("schema") != "serve_rungs":
            continue
        t = rec.get("transform_overhead") or {}
        raw = t.get("raw_req_per_sec")
        if raw is None:
            print(f"  transform overhead: r{rnd} predates the field (skip)")
            return []
        fails = []
        print(
            f"  transform overhead (r{rnd}): raw {raw:.1f} vs assembled "
            f"{t.get('assembled_req_per_sec', 0):.1f} req/s "
            f"(+{t.get('transform_us_per_row', 0)}us/row, "
            f"retraces={t.get('raw_retraces', 0)})"
        )
        if not t.get("assembled_bit_identical", True):
            fails.append(
                "raw-dict transform path not bit-identical to "
                f"pre-assembled vectors in {os.path.basename(path)}"
            )
        if t.get("raw_retraces"):
            fails.append(
                f"{t['raw_retraces']} steady-state retrace(s) on the "
                f"raw-dict transform path in {os.path.basename(path)} "
                "(the batched pipeline is leaking shapes)"
            )
        return fails
    print("  transform overhead: no serve_rungs artifact (skip)")
    return []


def check_fleet(old, new, tol: float) -> List[str]:
    """-> failure messages for the fleet pair (same replica count)."""
    (o_rnd, _o_path, o), (n_rnd, _n_path, n) = old, new
    fails = []
    floor = o["req_per_sec"] * (1.0 - tol)
    print(
        f"  fleet req/s ({n['replicas']} replicas): r{n_rnd} "
        f"{n['req_per_sec']:.1f} vs r{o_rnd} {o['req_per_sec']:.1f} "
        f"(floor {floor:.1f}, tol {tol:.0%})"
    )
    if n["req_per_sec"] < floor:
        fails.append(
            f"fleet throughput regressed: {n['req_per_sec']:.1f} < "
            f"{o['req_per_sec']:.1f} * (1 - {tol}) = {floor:.1f} "
            f"at {n['replicas']} replicas"
        )
    if o.get("p99_ms") is not None and n.get("p99_ms") is not None:
        ceil = o["p99_ms"] * (1.0 + tol)
        print(
            f"  fleet p99: r{n_rnd} {n['p99_ms']:.3f} ms vs r{o_rnd} "
            f"{o['p99_ms']:.3f} ms (ceiling {ceil:.3f})"
        )
        if n["p99_ms"] > ceil:
            fails.append(
                f"fleet p99 latency regressed: {n['p99_ms']:.3f} ms > "
                f"{o['p99_ms']:.3f} * (1 + {tol}) = {ceil:.3f} ms"
            )
    if n.get("retraces"):
        fails.append(
            f"fleet steady-state retraces: {n['retraces']} "
            "(a replica's ladder is leaking shapes — see health.retrace)"
        )
    return fails


def check_serve(old, new, tol: float) -> List[str]:
    """-> failure messages for one same-(metric, rung) serve pair."""
    (o_rnd, _o_path, o), (n_rnd, _n_path, n) = old, new
    fails = []
    label = n.get("label", "default")
    floor = o["req_per_sec"] * (1.0 - tol)
    print(
        f"  serve req/s [{label}]: r{n_rnd} {n['req_per_sec']:.1f} vs "
        f"r{o_rnd} {o['req_per_sec']:.1f} (floor {floor:.1f}, tol {tol:.0%})"
    )
    if n["req_per_sec"] < floor:
        fails.append(
            f"serve throughput regressed on the {label} rung: "
            f"{n['req_per_sec']:.1f} < "
            f"{o['req_per_sec']:.1f} * (1 - {tol}) = {floor:.1f}"
        )
    if o.get("p99_ms") is not None and n.get("p99_ms") is not None:
        ceil = o["p99_ms"] * (1.0 + tol)
        print(
            f"  serve p99: r{n_rnd} {n['p99_ms']:.3f} ms vs r{o_rnd} "
            f"{o['p99_ms']:.3f} ms (ceiling {ceil:.3f})"
        )
        if n["p99_ms"] > ceil:
            fails.append(
                f"serve p99 latency regressed: {n['p99_ms']:.3f} ms > "
                f"{o['p99_ms']:.3f} * (1 + {tol}) = {ceil:.3f} ms"
            )
    if n.get("retraces"):
        fails.append(
            f"serve steady-state retraces: {n['retraces']} "
            "(the shape ladder is leaking shapes — see health.retrace)"
        )
    return fails


# ---------------------------------------------------------------------------
# Autoscaler ramp gate (SCALE_r*.json, serve_bench.py --ramp)
# ---------------------------------------------------------------------------


def find_scale_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted by round number (SCALE_r<NN>.json)."""
    out = []
    for path in glob.glob(os.path.join(repo, "SCALE_*.json")):
        m = re.search(r"SCALE_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def read_scale_record(path: str):
    """Normalized serve_scale ramp record (raw or CI-driver-wrapped), or
    None for anything else."""
    import json

    with open(path) as f:
        rec = json.load(f)
    if "parsed" in rec and "cmd" in rec:  # CI driver wrapper
        rec = rec["parsed"] or {}
    if rec.get("schema") != "serve_scale":
        return None
    return rec


def scale_comparable_pair(artifacts: List[Tuple[int, str]]):
    """Newest two ramp records sharing (metric, replicas_min,
    replicas_max) — a 1->4 ramp is a different workload than a 2->8 one,
    exactly like the fleet gate's same-replica-count rule."""
    usable = []
    for rnd, path in artifacts:
        try:
            rec = read_scale_record(path)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if rec and rec.get("metric") and rec.get("peak_replicas") is not None:
            usable.append((rnd, path, rec))
    if len(usable) < 2:
        return None
    newest = usable[-1]
    for older in reversed(usable[:-1]):
        if (older[2]["metric"] == newest[2]["metric"]
                and older[2].get("replicas_min") == newest[2].get("replicas_min")
                and older[2].get("replicas_max") == newest[2].get("replicas_max")):
            return older, newest
    return None


def check_scale_pair(old, new) -> List[str]:
    """-> failure messages for the same-(min,max) ramp pair: elasticity
    must not regress (a fleet that used to reach 4 replicas under the
    same ramp and now stalls at 2 lost its scale-up path)."""
    (o_rnd, _o_path, o), (n_rnd, _n_path, n) = old, new
    fails = []
    print(
        f"  ramp peak ({n.get('replicas_min')}->{n.get('replicas_max')}): "
        f"r{n_rnd} {n['peak_replicas']} vs r{o_rnd} {o['peak_replicas']} "
        "replicas"
    )
    if n["peak_replicas"] < o["peak_replicas"]:
        fails.append(
            f"ramp peak regressed: reached {n['peak_replicas']} replica(s) "
            f"vs {o['peak_replicas']} under the same "
            f"[{n.get('replicas_min')}, {n.get('replicas_max')}] band"
        )
    return fails


def check_scale_absolute(artifacts: List[Tuple[int, str]]) -> List[str]:
    """Absolute gate on the NEWEST ramp artifact: the acceptance facts it
    recorded must still hold (zero failures, shrink completed, sheds
    confined to the pre-scale window) — a hand-edited or stale artifact
    cannot quietly ship a broken elasticity story."""
    for rnd, path in reversed(artifacts):
        try:
            rec = read_scale_record(path)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if rec is None:
            continue
        fails = []
        name = os.path.basename(path)
        print(
            f"  ramp (r{rnd}): peak={rec.get('peak_replicas')} "
            f"end={rec.get('end_replicas')} failures={rec.get('failures')} "
            f"sheds={rec.get('shed_429')} "
            f"(after peak: {rec.get('sheds_after_peak')})"
        )
        if rec.get("failures"):
            fails.append(
                f"ramp artifact {name} records {rec['failures']} request "
                "failure(s) — the zero-loss contract is broken"
            )
        if rec.get("end_replicas") != rec.get("replicas_min"):
            fails.append(
                f"ramp artifact {name} ended at {rec.get('end_replicas')} "
                f"replica(s), not the {rec.get('replicas_min')} floor"
            )
        if rec.get("sheds_after_peak"):
            fails.append(
                f"ramp artifact {name} records "
                f"{rec['sheds_after_peak']} shed(s) after the fleet "
                "reached its peak (sheds must be pre-scale only)"
            )
        return fails
    print("  ramp: no serve_scale artifact (skip)")
    return []


# ---------------------------------------------------------------------------
# GOSS ablation gate (within-artifact arm comparison)
# ---------------------------------------------------------------------------

GOSS_BASE_ARMS = ("part", "b256", "nopart")


def find_ablation_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted by round number (ABLATION_r<NN>.json)."""
    out = []
    for path in glob.glob(os.path.join(repo, "ABLATION_*.json")):
        m = re.search(r"ABLATION_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def read_goss_arms(path: str):
    """(goss arms dict, baseline arm name, configs) from an ablation
    artifact, or None when the artifact has no goss arm + baseline pair
    (pre-r11 artifacts skip cleanly)."""
    import json

    with open(path) as f:
        rec = json.load(f)
    configs = rec.get("configs") or {}
    goss_arms = {
        name: cfg for name, cfg in configs.items()
        if name.startswith("goss") and cfg.get("steady_trees_per_sec")
    }
    base = next(
        (a for a in GOSS_BASE_ARMS
         if configs.get(a, {}).get("steady_trees_per_sec")),
        None,
    )
    if not goss_arms or base is None:
        return None
    return goss_arms, base, configs


def check_goss(rnd: int, path: str, arms, tol_auc: float, min_speedup: float):
    """-> failure messages for the within-artifact GOSS arm comparison."""
    goss_arms, base, configs = arms
    fails = []
    b = configs[base]
    b_tps = float(b["steady_trees_per_sec"])
    b_auc = b.get("auc")
    for name, cfg in sorted(goss_arms.items()):
        tps = float(cfg["steady_trees_per_sec"])
        ratio = tps / max(b_tps, 1e-12)
        print(
            f"  goss win-rate (r{rnd}): {name} {tps:.3f} vs {base} "
            f"{b_tps:.3f} trees/s = {ratio:.2f}x (floor {min_speedup:.2f}x)"
        )
        if ratio < min_speedup:
            fails.append(
                f"GOSS arm {name!r} lost its speedup: {ratio:.2f}x vs "
                f"{base!r} in {os.path.basename(path)} "
                f"(floor {min_speedup:.2f}x, env GOSS_MIN_SPEEDUP)"
            )
        auc = cfg.get("auc")
        if auc is not None and b_auc is not None:
            drop = float(b_auc) - float(auc)
            print(
                f"  goss quality (r{rnd}): {name} auc {float(auc):.4f} vs "
                f"{base} {float(b_auc):.4f} (drop {drop:.4f}, "
                f"tol {tol_auc})"
            )
            # one-sided: only a quality LOSS trips the gate (short-run
            # amplification reading high is not a failure); NaN fails
            if not (drop <= tol_auc):
                fails.append(
                    f"GOSS arm {name!r} lost {drop:.4f} AUC vs "
                    f"{base!r} in {os.path.basename(path)} (tol {tol_auc}, "
                    "env GOSS_AUC_TOL)"
                )
    return fails


# ---------------------------------------------------------------------------
# PROF (ytkprof drill) artifacts — compile-cost gate
# ---------------------------------------------------------------------------


def find_prof_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted (PROF_r<NN>.json — scripts/prof_drill.py)."""
    out = []
    for path in glob.glob(os.path.join(repo, "PROF_*.json")):
        m = re.search(r"PROF_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def _prof_identity(rec: dict) -> tuple:
    """Comparable = same drill metric at the same workload shape — a
    bigger drill in a later round must not gate against a smaller one."""
    shape = rec.get("train", {}).get("shape", {})
    return (rec.get("metric"), shape.get("rows"), shape.get("trees"))


def prof_comparable_pair(artifacts: List[Tuple[int, str]]):
    """(older, newest) ytkprof_drill records with matching identity, or
    None. Unreadable / wrong-schema artifacts are skipped, not fatal."""
    usable = []
    for rnd, path in artifacts:
        try:
            with open(path) as f:
                rec = json.load(f)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if rec.get("schema") != "ytkprof_drill":
            print(f"  [skip] {os.path.basename(path)}: schema "
                  f"{rec.get('schema')!r} is not ytkprof_drill")
            continue
        usable.append((rnd, path, rec))
    if not usable:
        return None, None
    newest = usable[-1]
    for older in reversed(usable[:-1]):
        if _prof_identity(older[2]) == _prof_identity(newest[2]):
            return older, newest
    return None, newest


def check_prof_absolute(newest) -> List[str]:
    """Newest drill alone: steady-state retrace count must be zero (the
    ladder/AOT contract — any post-warmup compile is a found bug)."""
    rnd, path, rec = newest
    fails = []
    retraces = rec.get("retraces")
    print(f"  prof retraces (r{rnd}): {retraces}")
    if retraces != 0:
        fails.append(
            f"steady-state retraces in {os.path.basename(path)}: "
            f"{retraces} != 0 (see the compile ledger entries in the "
            "artifact — each names the program + signature diff)"
        )
    return fails


def check_prof(old, new, tol: float) -> List[str]:
    """Pair gate: total compile ms within band of the predecessor.
    Compile time is jit-cache/machine sensitive, so the default band is
    wide (PROF_COMPILE_TOL, fractional growth allowed)."""
    (o_rnd, o_path, o), (n_rnd, n_path, n) = old, new
    fails = []
    o_ms = (o.get("compile") or {}).get("total_ms")
    n_ms = (n.get("compile") or {}).get("total_ms")
    if o_ms is None or n_ms is None:
        print("  [skip] prof pair: artifact lacks compile.total_ms")
        return fails
    ceil = o_ms * (1.0 + tol)
    print(
        f"  compile cost: r{n_rnd} {n_ms:.0f} ms vs r{o_rnd} {o_ms:.0f} ms "
        f"(ceiling {ceil:.0f} ms, tol {tol:.0%})"
    )
    if n_ms > ceil:
        fails.append(
            f"compile cost grew: {n_ms:.0f} ms > {o_ms:.0f} ms * "
            f"(1 + {tol}) = {ceil:.0f} ms (per-program breakdown in "
            f"{os.path.basename(n_path)} compile.by_program; "
            "env PROF_COMPILE_TOL)"
        )
    return fails


# ---------------------------------------------------------------------------
# MESH (mesh-obs drill) artifacts — per-model isolation gate
# ---------------------------------------------------------------------------


def find_mesh_artifacts(repo: str) -> List[Tuple[int, str]]:
    """[(round, path)] sorted (MESH_r<NN>.json — scripts/mesh_drill.py)."""
    out = []
    for path in glob.glob(os.path.join(repo, "MESH_*.json")):
        m = re.search(r"MESH_r?(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def _mesh_identity(rec: dict) -> tuple:
    """Comparable = same drill metric, fleet size, and model cast — a
    3-model 2-replica drill must not gate against a different shape."""
    return (
        rec.get("metric"),
        rec.get("replicas"),
        tuple(sorted((rec.get("models") or {}).keys())),
    )


def mesh_comparable_pair(artifacts: List[Tuple[int, str]]):
    """(older, newest) ytkmesh_drill records with matching identity, or
    None. Unreadable / wrong-schema artifacts are skipped, not fatal."""
    usable = []
    for rnd, path in artifacts:
        try:
            with open(path) as f:
                rec = json.load(f)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if rec.get("schema") != "ytkmesh_drill":
            print(f"  [skip] {os.path.basename(path)}: schema "
                  f"{rec.get('schema')!r} is not ytkmesh_drill")
            continue
        usable.append((rnd, path, rec))
    if not usable:
        return None, None
    newest = usable[-1]
    for older in reversed(usable[:-1]):
        if _mesh_identity(older[2]) == _mesh_identity(newest[2]):
            return older, newest
    return None, newest


def check_mesh_absolute(newest) -> List[str]:
    """Newest drill alone: the tenant-isolation invariants are absolute,
    not relative — the abusive model's burn sentinel fired BY NAME, the
    quiet models' sentinels stayed silent, and per-model counters summed
    exactly to their global twins on every replica (conservation)."""
    rnd, path, rec = newest
    base = os.path.basename(path)
    fails = []
    iso = rec.get("burn_isolation") or {}
    print(
        f"  mesh burn isolation (r{rnd}): abusive {iso.get('abusive')!r} "
        f"fired {iso.get('abusive_fired')}, quiet fired "
        f"{iso.get('quiet_fired')}"
    )
    if not iso.get("ok"):
        fails.append(
            f"burn isolation broke in {base}: abusive model "
            f"{iso.get('abusive')!r} fired {iso.get('abusive_fired')} "
            f"window(s), quiet models fired {iso.get('quiet_fired')} "
            "(want >=1 and ==0)"
        )
    cons = rec.get("conservation") or {}
    print(f"  mesh conservation (r{rnd}): ok={cons.get('ok')}")
    if not cons.get("ok"):
        fails.append(
            f"per-model counter conservation broke in {base}: "
            "sum(serve.model.*.<c>) != serve.<c> on some replica "
            "(see conservation.per_replica)"
        )
    if not rec.get("ok"):
        fails.append(
            f"mesh drill recorded failures in {base}: "
            f"{rec.get('failures')}"
        )
    return fails


def check_mesh(old, new, tol: float) -> List[str]:
    """Pair gate: the QUIET models' fleet p99 within band of the
    predecessor — the accounting plane must not tax the tenants it
    protects. The abusive model's latency is the drill's subject
    (saturated by design), so it is exempt. Band is wide by default
    (MESH_P99_TOL): micro-fleet latency on a shared box is noisy."""
    (o_rnd, o_path, o), (n_rnd, n_path, n) = old, new
    fails = []
    abusive = (n.get("burn_isolation") or {}).get("abusive")
    for name in sorted((n.get("models") or {})):
        if name == abusive:
            continue
        o_p99 = ((o.get("models") or {}).get(name) or {}).get(
            "latency", {}).get("p99_ms")
        n_p99 = ((n.get("models") or {}).get(name) or {}).get(
            "latency", {}).get("p99_ms")
        if o_p99 is None or n_p99 is None or o_p99 <= 0:
            print(f"  [skip] mesh pair {name!r}: missing fleet p99")
            continue
        ceil = o_p99 * (1.0 + tol)
        print(
            f"  mesh quiet p99 {name!r}: r{n_rnd} {n_p99:.2f} ms vs "
            f"r{o_rnd} {o_p99:.2f} ms (ceiling {ceil:.2f} ms, "
            f"tol {tol:.0%})"
        )
        if n_p99 > ceil:
            fails.append(
                f"quiet model {name!r} fleet p99 regressed: "
                f"{n_p99:.2f} ms > {o_p99:.2f} ms * (1 + {tol}) in "
                f"{os.path.basename(n_path)} (env MESH_P99_TOL)"
            )
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="repo root holding BENCH_*.json (default: this repo)",
    )
    ap.add_argument(
        "--tol",
        type=float,
        default=float(os.environ.get("BENCH_REGRESS_TOL", "0.15")),
        help="allowed fractional throughput drop (default 0.15; "
        "env BENCH_REGRESS_TOL)",
    )
    args = ap.parse_args(argv)

    artifacts = find_artifacts(args.dir)
    print(f"check_bench_regress: {len(artifacts)} BENCH artifact(s) in {args.dir}")
    pair = comparable_pair(artifacts)
    fails: List[str] = []
    if pair is None:
        print("check_bench_regress: SKIP train gate (fewer than two "
              "comparable artifacts)")
    else:
        fails += check(*pair, tol=args.tol)

    serve_artifacts = find_serve_artifacts(args.dir)
    print(f"check_bench_regress: {len(serve_artifacts)} SERVE artifact(s)")
    serve_pairs = serve_comparable_pairs(serve_artifacts)
    if not serve_pairs:
        print("check_bench_regress: SKIP serve gate (no same-rung "
              "comparable pairs)")
    else:
        for pair in serve_pairs:
            fails += check_serve(*pair, tol=args.tol)
    fails += check_rung_quality(serve_artifacts)
    fails += check_tracing_overhead(serve_artifacts, tol=args.tol)
    fails += check_quality_overhead(serve_artifacts, tol=args.tol)
    fails += check_transform_overhead(serve_artifacts)

    fleet_pair = fleet_comparable_pair(serve_artifacts)
    if fleet_pair is None:
        print("check_bench_regress: SKIP fleet gate (no same-(metric, "
              "replicas, rung) fleet pair)")
    else:
        fails += check_fleet(*fleet_pair, tol=args.tol)

    # autoscaler ramp gate: newest SCALE artifact re-gated absolutely,
    # plus same-(min,max) pair comparison when a predecessor exists
    scale_artifacts = find_scale_artifacts(args.dir)
    print(f"check_bench_regress: {len(scale_artifacts)} SCALE artifact(s)")
    fails += check_scale_absolute(scale_artifacts)
    scale_pair = scale_comparable_pair(scale_artifacts)
    if scale_pair is None:
        print("check_bench_regress: SKIP ramp pair gate (no same-(metric, "
              "min, max) ramp pair)")
    else:
        fails += check_scale_pair(*scale_pair)

    # GOSS gate: newest ablation artifact with goss + baseline arms
    ablations = find_ablation_artifacts(args.dir)
    print(f"check_bench_regress: {len(ablations)} ABLATION artifact(s)")
    goss_arms = None
    for rnd, path in reversed(ablations):
        try:
            goss_arms = read_goss_arms(path)
        except Exception as e:  # noqa: BLE001 — a rotten artifact is a skip
            print(f"  [skip] {os.path.basename(path)}: unreadable ({e})")
            continue
        if goss_arms is not None:
            fails += check_goss(
                rnd, path, goss_arms,
                tol_auc=float(os.environ.get("GOSS_AUC_TOL", "0.005")),
                min_speedup=float(os.environ.get("GOSS_MIN_SPEEDUP", "1.0")),
            )
            break
    if goss_arms is None:
        print("check_bench_regress: SKIP goss gate (no ablation artifact "
              "with goss + baseline arms)")

    # compile-cost gate: newest ytkprof drill re-gated absolutely
    # (retraces == 0), plus a compile-ms band vs a comparable predecessor
    prof_artifacts = find_prof_artifacts(args.dir)
    print(f"check_bench_regress: {len(prof_artifacts)} PROF artifact(s)")
    prof_older, prof_newest = prof_comparable_pair(prof_artifacts)
    if prof_newest is not None:
        fails += check_prof_absolute(prof_newest)
    if prof_older is None:
        print("check_bench_regress: SKIP prof pair gate (fewer than two "
              "comparable PROF artifacts)")
    else:
        fails += check_prof(
            prof_older, prof_newest,
            tol=float(os.environ.get("PROF_COMPILE_TOL", "0.75")),
        )

    # mesh-obs gate: newest per-model isolation drill re-gated absolutely
    # (burn named the tenant, conservation exact), plus a quiet-model p99
    # band vs a comparable predecessor
    mesh_artifacts = find_mesh_artifacts(args.dir)
    print(f"check_bench_regress: {len(mesh_artifacts)} MESH artifact(s)")
    mesh_older, mesh_newest = mesh_comparable_pair(mesh_artifacts)
    if mesh_newest is not None:
        fails += check_mesh_absolute(mesh_newest)
    if mesh_older is None:
        print("check_bench_regress: SKIP mesh pair gate (fewer than two "
              "comparable MESH artifacts)")
    else:
        fails += check_mesh(
            mesh_older, mesh_newest,
            tol=float(os.environ.get("MESH_P99_TOL", "0.75")),
        )

    if fails:
        for f in fails:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("check_bench_regress: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
