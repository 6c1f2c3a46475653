"""BENCHMARK.json and the files it names. A cell, a configuration, a family
or a per-layer metric is found by its name, so adding one is adding an entry
and files, never an edit here."""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(
        f"perfbench: no {what} named {name!r} in BENCHMARK.json "
        f"(known: {[e['name'] for e in entries]})"
    )


def load_module(kind: str, name: str):
    """perfbench/<kind>/<name>.py, loaded by path (metric names hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: {kind} {name!r} has no file {path}")
    mod_name = f"perfbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with its configuration and traffic files."""

    def __init__(self, bm: dict, name: str):
        self.bm = bm
        self.entry = by_name(bm["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_entry = by_name(bm["configs"], self.entry["config"], "config")
        self.config = load_json(os.path.join(ROOT, self.config_entry["file"]))
        self.traffic = load_json(
            os.path.join(BENCH_DIR, "workloads", name + ".json")
        )
        self.sizes = self.config["sizes"]

    def metrics(self, group: str):
        """The cell's metrics of `end_to_end` or `per_layer`."""
        return [
            m for m in self.bm[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


def validate(bm: dict) -> list:
    """The contract's rules that a file alone can break; returns complaints."""
    bad = []
    names = set()

    def name_ok(n, what):
        if not NAME_RE.match(str(n)):
            bad.append(f"{what} {n!r}: not a permitted name")

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in bm[group]:
            name_ok(e["name"], group)
            if e["name"] in seen:
                bad.append(f"{group}: {e['name']} twice")
            seen.add(e["name"])
    cells = {w["name"]: w for w in bm["workloads"]}
    cfgs = {c["name"] for c in bm["configs"]}
    pairs = set()
    for w in bm["workloads"]:
        name_ok(w["config"], "config of workload")
        name_ok(w["traffic"], "traffic")
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why is not 1..200 chars on a line")
    for c in bm["configs"]:
        for k in c["reduced"]:
            name_ok(k, f"reduced key of {c['name']}")
        if not any(w["config"] == c["name"] for w in bm["workloads"]):
            bad.append(f"config {c['name']}: used by no cell")
        if not c["file"].startswith(tuple(p + "/" for p in bm["paths"])):
            bad.append(f"config {c['name']}: file outside paths")
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")

    def cells_of(m):
        return set(m.get("workloads", cells))

    for group in ("end_to_end", "per_layer"):
        for m in bm[group]:
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"{m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source {m['source']!r}")
            if m["name"] in names:
                bad.append(f"metric {m['name']} twice")
            names.add(m["name"])
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append(f"{m['name']}: unknown workload {w}")
    for m in bm["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    for m in bm["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']}")
            continue
        missing = cells_of(m) - cells_of(e2e[m["moves"]])
        if missing:
            bad.append(
                f"{m['name']}: cells {sorted(missing)} do not report {m['moves']}"
            )
        if not os.path.exists(
            os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
        ):
            bad.append(f"{m['name']}: no reader perfbench/metrics/{m['name']}.py")
    for w in cells:
        have_e2e = [m for m in bm["end_to_end"] if w in cells_of(m)]
        if len([m for m in have_e2e if m["name"] != "setup_s"]) < 1:
            bad.append(f"cell {w}: no end-to-end metric besides setup_s")
        if not any(w in cells_of(m) for m in bm["per_layer"]):
            bad.append(f"cell {w}: no per-layer metric")
    if not 1 <= int(bm["run_seconds"]) <= 51:
        bad.append(f"run_seconds {bm['run_seconds']}")
    four = sum(1 for w in bm["workloads"] if w["chips"] == 4)
    if four > max(1, len(bm["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(bm['workloads'])}")
    return bad
