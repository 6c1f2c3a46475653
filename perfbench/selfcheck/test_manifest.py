"""BENCHMARK.json against the contract's rules that a file alone can break,
and every name it gives against the files that have to be there."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from pb import manifest  # noqa: E402

KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_benchmark_json_keeps_the_contract():
    bm = manifest.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert manifest.validate(bm) == []
    for group, keys in KEYS.items():
        for e in bm[group]:
            assert keys <= set(e) <= keys | {"workloads"}, (group, e["name"])
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) < 64 << 10
    assert len(bm["command"]) <= 32 and all(
        not w.startswith("/") and ".." not in w for w in bm["command"])


def test_every_cell_has_its_files_and_every_metric_a_reader():
    bm = manifest.benchmark()
    for w in bm["workloads"]:
        cell = manifest.Cell(bm, w["name"])
        assert os.path.exists(os.path.join(BENCH, "families", cell.config["family"] + ".py"))
        assert cell.config["rate"]["metric"] in {m["name"] for m in cell.metrics("end_to_end")}
        for m in cell.metrics("per_layer"):
            assert callable(manifest.load_module("metrics", m["name"]).read)
        for key in cell.config_entry["reduced"]:
            assert key in json.dumps(cell.config)


def test_a_broken_manifest_is_caught():
    bm = manifest.benchmark()
    bm["per_layer"].append({"name": "bad name", "unit": "tokens per s", "better": "up",
                            "source": "guess", "layer": "x", "moves": "nothing"})
    bad = "\n".join(manifest.validate(bm))
    for word in ("not a permitted name", "unit", "better", "source", "moves"):
        assert word in bad
