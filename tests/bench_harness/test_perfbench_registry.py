"""The benchmark finds every piece of a cell by name, and a new
configuration, traffic mix or metric is a new file: adding one edits no
file that is there (only entries in BENCHMARK.json)."""

import hashlib
import json
import os
import re
import shutil

import pytest

from perfbench import registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = registry.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = registry.load_cell(ROOT, cell)
    assert c.chips in (1, 4)
    assert {"inputs", "share_keys", "warmup_jobs_per_input",
            "traced_jobs"} <= set(c.traffic)
    ref = registry.reference(ROOT, c.config["reference"])
    assert ref.LIMITS and hasattr(ref, "Reference")
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(registry.metric_reader(ROOT, m["name"]).read)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in BENCH["configs"] + BENCH["workloads"]
             + metrics]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        cfg = registry.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(cfg["reduced"])


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_addition_is_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "out",
                                                  "fixtures"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root)

    cfg = registry.load_json(os.path.join(
        root, "perfbench/configs/terasort_hibench_100b.json"))
    cfg.update(name="terasort_52b", val_words=11)
    with open(os.path.join(root, "perfbench/configs/terasort_52b.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "perfbench/traffic/one_input.json"),
              "w") as f:
        json.dump({"inputs": 1,
                   "share_keys": True, "warmup_jobs_per_input": 1,
                   "traced_jobs": 4}, f)
    with open(os.path.join(root, "perfbench/metrics/jobs_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return run.window.jobs\n")
    bench = registry.load_benchmark(root)
    bench["configs"].append({"name": "terasort_52b", "source": "x",
                             "file": "perfbench/configs/terasort_52b.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "terasort_52b_1chip",
                               "config": "terasort_52b",
                               "traffic": "one_input", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "jobs_in_window", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "window", "moves": "job_p95_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = registry.load_cell(root, "terasort_52b_1chip")
    assert cell.config["val_words"] == 11
    assert cell.traffic["inputs"] == 1
    assert "jobs_in_window" in [m["name"] for m in cell.per_layer]

    class Run:
        class window:
            jobs = 7
    assert registry.metric_reader(root, "jobs_in_window").read(Run) == 7
    after = _digests(root)
    assert {p: h for p, h in after.items() if p in before} == before


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        registry.load_cell(ROOT, "no_such_cell")


def test_traffic_asking_for_what_is_not_run_is_refused(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench", "traffic"),
                    os.path.join(root, "perfbench", "traffic"))
    shutil.copytree(os.path.join(ROOT, "perfbench", "configs"),
                    os.path.join(root, "perfbench", "configs"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cell = BENCH["workloads"][0]
    path = os.path.join(root, "perfbench", "traffic",
                        cell["traffic"] + ".json")
    mix = registry.load_json(path)
    mix["loop"] = "open"
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(ValueError, match="loop"):
        registry.load_cell(root, cell["name"])
