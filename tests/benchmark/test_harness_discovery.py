"""Discovery by name: a configuration, a mix, a cell and a metric added
as new files plus entries in BENCHMARK.json are found and run with no
edit to the harness; and BENCHMARK.json keeps to the benchmark's
contract."""

import json
import os
import re
import time

import pytest

import bench_tiny

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_json(os.path.join(bench_tiny.REPO, "BENCHMARK.json"))


def test_new_files_and_entries_run_without_an_edit(tmp_path, capsys):
    root = bench_tiny.make_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    cfg = harness.load_json(os.path.join(bench, "configs",
                                         "fleet1024x10k.json"))
    cfg.update(name="fleet3x40", pairs=3, doc_nodes=41)
    with open(os.path.join(bench, "configs", "fleet3x40.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "mixes", "div3.json"), "w") as f:
        json.dump({"shared_values": 37, "divergent_values": 3,
                   "hide_every": 2}, f)
    with open(os.path.join(bench, "metrics", "calls_n.merge.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.data['calls'])\n")
    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({"name": "fleet3x40", "source": "a test",
                            "file": "benchmark/configs/fleet3x40.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "merge.fleet3x40.div3",
                              "config": "fleet3x40", "traffic": "div3",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "merge_ms":
            m["workloads"].append("merge.fleet3x40.div3")
    spec["end_to_end"].append({"name": "calls_n.merge", "unit": "calls",
                               "better": "higher", "bound": 0.5,
                               "source": "host_clock",
                               "workloads": ["merge.fleet3x40.div3"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    cell = harness.Cell(root, "merge.fleet3x40.div3")
    assert cell.config["pairs"] == 3 and cell.mix["divergent_values"] == 3
    assert [m["name"] for m in cell.metrics("end_to_end")] == \
        ["merge_ms", "setup_s", "calls_n.merge"]
    code = harness.run_cell("merge.fleet3x40.div3", 11, 0.5, False,
                            time.perf_counter(), root=root,
                            check_device=False)
    assert code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out
    assert out["metrics"]["calls_n.merge"]["value"] >= 1
    assert out["attempted"] == 3 * out["metrics"]["calls_n.merge"]["value"]


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.Cell(bench_tiny.REPO, "no.such.cell")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(SPEC["command"]) <= 32
    assert os.path.getsize(os.path.join(bench_tiny.REPO,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_every_file_the_spec_names_lies_under_paths():
    paths = SPEC["paths"]
    for word in SPEC["command"][1:]:
        assert any(word.startswith(p + "/") for p in paths), word
    for c in SPEC["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert os.path.isfile(os.path.join(bench_tiny.REPO, c["file"]))


def test_names_units_and_entry_keys():
    e2e_keys = {"name", "unit", "better", "bound", "source"}
    layer_keys = {"name", "unit", "better", "source", "layer", "moves"}
    names = []
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == e2e_keys
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        names.append(m["name"])
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == layer_keys
        assert m["moves"] in names
        names.append(m["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(bench_tiny.REPO, "benchmark",
                                           "metrics", m["name"] + ".py"))
    cells = [w["name"] for w in SPEC["workloads"]]
    configs = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    assert len(set(cells)) == len(cells) and len(set(configs)) == len(configs)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(bench_tiny.REPO, "benchmark",
                                           "mixes", w["traffic"] + ".json"))
    assert {w["config"] for w in SPEC["workloads"]} == set(configs)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in SPEC["workloads"]:
        cell = harness.Cell(bench_tiny.REPO, w["name"])
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
    assert layers == {"host assembly", "device kernel", "device",
                      "admission and journal", "tick and waves"}
