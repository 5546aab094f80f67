"""A new configuration, traffic mix and metric need only new files and
new entries in ``BENCHMARK.json``: no file that is there is edited."""

import hashlib
import json
from pathlib import Path

import pytest

from conftest import add_tiny_cells, copy_layout
from harness.layout import Layout, LayoutError

READER = '''
def read(ctx):
    if ctx.trace is None:
        return None
    return 42.0
'''


def _digests(root: Path):
    files = [root / "BENCHMARK.json"] + sorted(
        p for p in (root / "bench").rglob("*") if p.is_file())
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = copy_layout(tmp_path)
    before = _digests(root)
    add_tiny_cells(root)
    (root / "bench" / "metrics" / "throwaway_metric.py").write_text(READER)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "throwaway_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "ingest_ms_p95",
                              "workloads": ["tiny_2d.refresh"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root)
    edited = [p for p, h in before.items()
              if p != Path("BENCHMARK.json") and after.get(p) != h]
    assert edited == []

    lay = Layout(root)
    assert lay.config("tiny_2d")["scheme"]["level"] == 5
    assert lay.traffic("tiny_refresh")["refresh"]["clients"] == 2
    assert lay.checks("tiny_2d.query")["answer_gap"]["limit"] > 0
    names = [m["name"] for m in lay.per_layer("tiny_2d.refresh")]
    assert "throwaway_metric" in names
    assert "eval_ms_per_query" not in names
    assert [m["name"] for m in lay.end_to_end("tiny_2d.refresh")] == \
        ["ingest_ms_p95", "ingests_per_s", "setup_s"]
    reader = lay.metric_reader("throwaway_metric")
    assert reader.read(type("C", (), {"trace": object()})()) == 42.0
    assert lay.reference("combination_multilinear").reference_values


def test_metric_without_workloads_follows_its_end_to_end_metric(tmp_path):
    root = copy_layout(tmp_path)
    add_tiny_cells(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "m", "unit": "%", "better": "higher",
                              "source": "device_trace", "layer": "device",
                              "moves": "queries_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    lay = Layout(root)
    assert "m" in [m["name"] for m in lay.per_layer("tiny_2d.query")]
    assert "m" not in [m["name"] for m in lay.per_layer("tiny_2d.refresh")]


def test_a_missing_file_is_a_named_error(tmp_path):
    lay = Layout(copy_layout(tmp_path))
    with pytest.raises(LayoutError, match="no file"):
        lay.traffic("no_such_mix")
    with pytest.raises(LayoutError, match="no file"):
        lay.metric_reader("no_such_metric")
    with pytest.raises(LayoutError, match="no workload"):
        lay.workload("no.such")


def test_every_name_in_the_benchmark_has_its_files():
    from conftest import REPO
    lay = Layout(REPO)
    for w in lay.spec["workloads"]:
        lay.config(w["config"])
        lay.traffic(w["traffic"])
        lay.checks(w["name"])
        assert lay.per_layer(w["name"]), w["name"]
    for c in lay.spec["configs"]:
        lay.reference(lay.config(c["name"])["reference"])
    for m in lay.spec["per_layer"]:
        lay.metric_reader(m["name"])
