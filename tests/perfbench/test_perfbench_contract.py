"""BENCHMARK.json against the contract it is checked by, the files its
names lead to, and the rule that a later PR adds a cell, a configuration,
a traffic mix or a per-layer metric as NEW files and NEW entries only."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _perfbench_tiny import ROOT

from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("perfbench/") and c["file"] not in files
        files.add(c["file"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        body = harness.load_json(os.path.join(ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in ("entry", "assumed", "deployment", "check", "d_model",
                    "d_inner", "n_head", "n_layer"):
            assert key in body, (c["name"], key)
        assert body["d_model"] // body["n_head"] == 64


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "traffic", w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", m["name"] + ".py"))
        # listed cells report the end-to-end metric this one moves
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every cell: setup_s, one more end-to-end metric, a per-layer metric
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_layers_are_perf_md_layers(bench):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, "layer %r is not in PERF.md" % layer


def _digest(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha1(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_new_entries(tmp_path, bench):
    """A later PR's configuration, traffic mix and per-layer metric are
    found by the names in BENCHMARK.json with no edit to a file that is
    there: no registry, no list in run.py."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(os.path.join(root, "perfbench"))
    pb = os.path.join(root, "perfbench")
    cfg = dict(harness.load_json(os.path.join(
        pb, "configs", "transformer_base.json")), name="later_model")
    with open(os.path.join(pb, "configs", "later_model.json"), "w") as f:
        json.dump(cfg, f)
    mix = dict(harness.load_json(os.path.join(
        pb, "traffic", "open_poisson_0.7knee.json")), name="later_bursts",
        rate_rps=3.0)
    with open(os.path.join(pb, "traffic", "later_bursts.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(pb, "layer_metrics", "later_metric.py"),
              "w") as f:
        f.write("def read(records):\n    return records['answer']\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "later_model", "source": cfg["source"],
                           "file": "perfbench/configs/later_model.json",
                           "reduced": [], "why": "a later PR's model"})
    new["workloads"].append({"name": "later_cell", "config": "later_model",
                             "traffic": "later_bursts", "chips": 1,
                             "why": "a later PR's cell"})
    for m in new["end_to_end"]:
        if m["name"] == "ttft_p95_ms":
            m["workloads"] = m["workloads"] + ["later_cell"]
    new["per_layer"].append({"name": "later_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "serving host plane",
                             "moves": "ttft_p95_ms",
                             "workloads": ["later_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)

    cell = harness.Cell("later_cell", root=root)
    assert cell.config["name"] == "later_model"
    assert cell.traffic["rate_rps"] == 3.0
    assert cell.entry().__name__ == "perfbench_entry_frontend"
    names = {m["name"] for m in cell.per_layer()}
    assert names == {"build_s", "compile_s", "cache_misses", "later_metric"}
    got = harness.read_layer_metrics(cell, {
        "answer": 42, "setup": {"program_build": 1.0},
        "cache": {"compile_seconds": 2.0, "persistent_misses": 0}})
    assert got["later_metric"] == {"value": 42.0, "unit": "ms"}
    assert got["build_s"]["value"] == 1.0
    after = _digest(os.path.join(root, "perfbench"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/later_model.json", "traffic/later_bursts.json",
        "layer_metrics/later_metric.py"}


def test_a_new_entry_point_is_a_new_file(tmp_path, bench):
    """A later configuration that runs through another entry point of the
    program (a mesh, a router) brings ``entries/<entry>.py`` and names
    it."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = os.path.join(root, "perfbench")
    cfg = dict(harness.load_json(os.path.join(
        pb, "configs", "transformer_big.json")), name="later_mesh",
        entry="later_entry")
    with open(os.path.join(pb, "configs", "later_mesh.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(pb, "entries", "later_entry.py"), "w") as f:
        f.write("def run(ctx):\n    return 'later'\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "later_mesh", "source": cfg["source"],
                           "file": "perfbench/configs/later_mesh.json",
                           "reduced": [], "why": "a later PR's mesh"})
    new["workloads"].append({"name": "later_mesh_cell",
                             "config": "later_mesh",
                             "traffic": "train_fixed_batch", "chips": 4,
                             "why": "a later PR's four-chip cell"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    cell = harness.Cell("later_mesh_cell", root=root)
    assert cell.chips == 4 and cell.entry().run(None) == "later"
    same = harness.Cell("train_big_1chip", root=root)
    assert same.entry().__name__ == "perfbench_entry_executor"


def test_full_collections_inside_the_window_are_reported():
    import gc
    import time

    watch = harness.GcWatch()
    watch.start()
    t_open = time.perf_counter()
    gc.collect(0)
    gc.collect()
    inside = watch.stop(t_open, 60.0)
    assert len(inside) == 1 and inside[0][0] >= 0 and inside[0][1] > 0
    assert watch._event not in gc.callbacks
    gc.collect()
    assert len(watch.full) == 1
    assert watch.stop.__self__.full and not harness.GcWatch().full


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perfbench", "run.py")] + args,
        cwd=cwd, env=dict(os.environ, **env), capture_output=True,
        text=True, timeout=300)


def test_no_accelerator_is_an_error_and_prints_no_result():
    """The command itself never runs on the CPU: non-zero exit and no
    result line (this sandbox holds JAX to the CPU)."""
    done = _run(["--workload", "train_big_1chip", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], ROOT,
                JAX_PLATFORMS="cpu")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "NoAcceleratorError" in done.stderr


def test_benchmark_alone_is_not_the_system(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, no result."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "train_big_1chip", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
