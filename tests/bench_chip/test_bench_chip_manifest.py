"""``BENCHMARK.json`` and the files it names: every piece is found by name,
and a cell, a traffic mix or a metric is added by adding files and entries."""
import json
import re
import shutil

import pytest

from chipbench.manifest import BENCH_DIR, ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_named_file_is_found(workload):
    m = Manifest()
    cell = m.cell(workload)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    assert cell.traffic["kind"] == "tag_round"
    assert cell.limits
    names = {e["name"] for e in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for metric in cell.per_layer:
        assert callable(m.reader(metric["name"]))
        assert metric["moves"] in names


def test_the_manifest_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir()
    assert (ROOT / SPEC["command"][1]).is_file()
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_an_added_cell_traffic_and_metric_are_picked_up(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks" / "chip"
    traffic = json.loads((bench / "traffic" / "sync.c8.json").read_text())
    traffic["trainers"] = 4
    (bench / "traffic" / "sync.c4.json").write_text(json.dumps(traffic))
    shutil.copy(bench / "limits" / "tag.sync.c8.json", bench / "limits" / "tag.sync.c4.json")
    (bench / "metrics" / "rounds_seen.py").write_text(
        "def read(run):\n    return run.counters.get('rounds')\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tag.sync.c4", "config": "fedavg-sync-qwen2.5-3b-layer",
                              "traffic": "sync.c4", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "rounds_per_s", "unit": "1/s", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": ["tag.sync.c4"]})
    spec["per_layer"].append({"name": "rounds_seen", "unit": "rounds", "better": "higher",
                              "source": "host_clock", "layer": "aggregation fold",
                              "moves": "round_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    m = Manifest(tmp_path)
    cell = m.cell("tag.sync.c4")
    assert cell.traffic["trainers"] == 4
    # a metric with a workloads list goes to the cells it lists only
    assert {e["name"] for e in cell.end_to_end} == {"rounds_per_s", "setup_s"}
    assert {e["name"] for e in m.cell("tag.sync.c8").end_to_end} == {"round_s", "setup_s"}
    # one without goes to every cell reporting what it moves
    assert "rounds_seen" in {p["name"] for p in m.cell("tag.sync.c8").per_layer}
    assert "rounds_seen" not in {p["name"] for p in cell.per_layer}

    class Run:
        counters = {"rounds": 7}

    assert m.reader("rounds_seen")(Run) == 7


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_a_configuration_changes_only_what_it_lists_as_reduced(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    published = cfg.get("published", {})
    assert sorted(published) == sorted(entry["reduced"])
    for key, value in published.items():
        assert cfg[key] != value, key
    assert not {"vocab_size", "hidden_size", "intermediate_size"} & set(entry["reduced"])


def test_a_missing_file_is_named():
    with pytest.raises(KeyError, match="no workload"):
        Manifest().cell("no.such.cell")
