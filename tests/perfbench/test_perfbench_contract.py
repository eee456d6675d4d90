"""BENCHMARK.json against the contract it is checked by, the files its
names lead to, and the rule that a later PR adds a cell, a configuration
(of this model family or of another), a traffic mix or a per-layer metric
as NEW files and NEW entries only.

The contract's checks are plain functions of ``(bench, root)``, and so is
every other assertion under ``tests/perfbench/`` on BENCHMARK.json's lists
(``DECLARATIONS``): the tests call them on the real tree, and the tests
that rehearse a later PR's addition call them on a copy that holds the
addition. A later PR's own declaration test is one more such function: it
finds its entries by NAME, and ``test_an_addition_at_the_end_breaks_no_
declaration_test`` is what it has to keep passing."""

import copy
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import test_perfbench_admit_rows
import test_perfbench_cancel_rows
import test_perfbench_glm
import test_perfbench_host_ledger
import test_perfbench_jamba
import test_perfbench_trinity
from _perfbench_tiny import DECODER_SHARED, ROOT
from test_perfbench_program_records import check_new_metrics_declared

from perfbench import harness, loadgen

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a configuration's file says whatever its family: the sizes
# themselves go by the source's own keys
CONFIG_KEYS = ("name", "source", "entry", "precision", "reduced", "assumed",
               "deployment", "check")
CHECK_KEYS = ("what", "limits", "limits_why")
# Vaswani et al. 2017, table 3: the two configurations that are here keep
# their published widths (heads of d_model / n_head = 64)
TABLE_3 = {
    "transformer_big": {"d_model": 1024, "d_inner": 4096, "n_head": 16,
                        "n_layer": 6},
    "transformer_base": {"d_model": 512, "d_inner": 2048, "n_head": 8,
                         "n_layer": 6},
}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def check_top_level(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 65536
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def check_configs(bench, root):
    assert 1 <= len(bench["configs"]) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files, bodies = set(), {}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("perfbench/") and c["file"] not in files
        files.add(c["file"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        body = bodies[c["name"]] = harness.load_json(
            os.path.join(root, c["file"]))
        for key in CONFIG_KEYS:
            assert key in body, (c["name"], key)
        for key in CHECK_KEYS:
            assert key in body["check"], (c["name"], key)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert os.path.exists(os.path.join(
            root, "perfbench", "entries", body["entry"] + ".py"))
        # the model-configs guide, section 4: what was cut from the source
        # stands in the file beside the source's own value
        for key in c["reduced"]:
            assert NAME.match(key) and key in body, (c["name"], key)
            assert key in body.get("published", {}), (c["name"], key)
    for name, widths in TABLE_3.items():
        body = bodies[name]
        assert {k: body[k] for k in widths} == widths, name
        assert body["d_model"] // body["n_head"] == 64


def check_workloads(bench, root):
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
            root, "perfbench", "traffic", w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def check_metrics(bench, root):
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
            root, "perfbench", "layer_metrics", m["name"] + ".py"))
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


def check_layers(bench, root):
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, "layer %r is not in PERF.md" % layer


CHECKS = {"top_level": check_top_level, "configs": check_configs,
          "workloads": check_workloads, "metrics": check_metrics,
          "layers": check_layers,
          "new_metrics": lambda bench, root: check_new_metrics_declared(
              bench)}


# every function under tests/perfbench/ that reads BENCHMARK.json's lists:
# the contract's own and each family's and each addition's declaration
DECLARATIONS = dict(CHECKS, **{
    mod.__name__[len("test_perfbench_"):]: mod.check_declared
    for mod in (test_perfbench_admit_rows, test_perfbench_cancel_rows,
                test_perfbench_host_ledger, test_perfbench_glm,
                test_perfbench_jamba, test_perfbench_trinity)})


def test_top_level_keys_and_sizes(bench):
    check_top_level(bench, ROOT)


def test_configs(bench):
    check_configs(bench, ROOT)


def test_workloads(bench):
    check_workloads(bench, ROOT)


def test_metrics(bench):
    check_metrics(bench, ROOT)


def test_layers_are_perf_md_layers(bench):
    check_layers(bench, ROOT)


def _copy_perfbench(tmp_path):
    """A root under ``tmp_path`` that holds a copy of ``perfbench/``."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


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
    root = _copy_perfbench(tmp_path)
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
    root = _copy_perfbench(tmp_path)
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


# -- a later PR's configuration of ANOTHER family ----------------------------

# none of the Transformer's keys: a decoder-only model with latent attention
# (20 heads on a hidden size of 2048, head widths 192+64 and 256, low-rank
# projections) and routed experts (64, top 4, one shared; two FFN widths),
# cut in depth and in positions with the source's values beside the cuts
LATER_LM = {
    "name": "later_lm",
    "source": "https://example.org/later-lm/blob/main/config.json",
    "entry": "later_lm",
    "hidden_size": 2048, "num_attention_heads": 20,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "q_lora_rank": 768, "kv_lora_rank": 512,
    "n_routed_experts": 64, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "intermediate_size": 10240, "moe_intermediate_size": 1536,
    "first_k_dense_replace": 1, "vocab_size": 154880,
    "num_hidden_layers": 6, "max_position_embeddings": 1536,
    "published": {"num_hidden_layers": 47,
                  "max_position_embeddings": 202752},
    "reduced": ["num_hidden_layers", "max_position_embeddings"],
    "precision": "bfloat16 weights, latent rows and activations, float32 "
                 "accumulation and router scores, greedy decoding",
    "pool": {"num_slots": 256, "page_size": 16, "row": 576},
    "assumed": {"pool": "256 slots of 1536 positions, one 576-wide latent "
                        "row a token a layer"},
    "deployment": "one v5e chip holds the leading dense layer and 5 expert "
                  "layers with all 64 experts; further layers would lie on "
                  "further chips, as the stages of a pipeline",
    "check": {
        "what": "for the sampled requests of the window: the widest gap by "
                "which a served token's logit lies below the reference's "
                "best, under the program's choice of experts",
        "limits": {"served_logit_gap": 0.05},
        "limits_why": "a later PR's readings on the chip",
    },
}
LATER_ENTRY = """def run(ctx):
    return "later_lm"


def make_checker(cell, devices):
    return None
"""
LATER_READER = """def read(records):
    return (records.get("later") or {}).get(%r)
"""
LATER_METRICS = [
    {"name": "later_weights_s", "unit": "s", "better": "lower",
     "source": "host_clock", "layer": "set-up", "moves": "setup_s"},
    {"name": "later_admits_per_dispatch", "unit": "count",
     "better": "higher", "source": "program_counter",
     "layer": "serving host plane", "moves": "serve_tokens_per_s"},
    {"name": "later_grouped_experts_roofline", "unit": "%",
     "better": "higher", "source": "device_trace", "layer": "kernels",
     "moves": "serve_tokens_per_s"},
]
LATER_FILES = {"configs/later_lm.json", "entries/later_lm.py",
               "traffic/later_closed_lognormal.json"} | {
    "layer_metrics/%s.py" % m["name"] for m in LATER_METRICS}


def _add_second_family(root, bench):
    """What the next ``model_config`` PR adds, at the contract's level:
    new files under ``root``'s ``perfbench/`` and new entries at the END of
    a copy of ``bench``, which it writes to ``root`` and returns."""
    pb = os.path.join(root, "perfbench")
    with open(os.path.join(pb, "configs", "later_lm.json"), "w") as f:
        json.dump(LATER_LM, f)
    with open(os.path.join(pb, "entries", "later_lm.py"), "w") as f:
        f.write(LATER_ENTRY)
    # the keys of the closed mix that is there; output lengths drawn on
    # their own and not as a share of the prompt's
    mix = dict(harness.load_json(os.path.join(
        pb, "traffic", "closed_320_clients.json")),
        name="later_closed_lognormal", clients=288,
        src_len={"dist": "lognormal", "median": 400, "sigma": 0.8,
                 "min": 16, "max": 1024},
        trg_len={"dist": "lognormal", "median": 120, "sigma": 0.7,
                 "min": 8, "max": 512})
    with open(os.path.join(pb, "traffic", mix["name"] + ".json"), "w") as f:
        json.dump(mix, f)
    for m in LATER_METRICS:
        with open(os.path.join(pb, "layer_metrics", m["name"] + ".py"),
                  "w") as f:
            f.write(LATER_READER % m["name"])
    new = copy.deepcopy(bench)
    new["configs"].append({
        "name": "later_lm", "source": LATER_LM["source"],
        "file": "perfbench/configs/later_lm.json",
        "reduced": LATER_LM["reduced"],
        "why": "latent attention and routed experts: another family"})
    new["workloads"].append({
        "name": "later_lm_saturated", "config": "later_lm",
        "traffic": "later_closed_lognormal", "chips": 1,
        "why": "closed loop, 288 callers on 256 slots: a later PR's cell"})
    for m in new["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"] = m["workloads"] + ["later_lm_saturated"]
    new["per_layer"] += [dict(m, workloads=["later_lm_saturated"])
                         for m in LATER_METRICS]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    return new


@pytest.fixture
def copied_tree(tmp_path, bench):
    """(a copy of the bench to add to, the root of a copy of the tree)."""
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(ROOT, "PERF.md"), root)
    return copy.deepcopy(bench), root


@pytest.fixture
def second_family(copied_tree):
    """(the bench with the addition, the root of the copy that holds it,
    the digest of the copy's ``perfbench/`` before the addition)."""
    bench, root = copied_tree
    before = _digest(os.path.join(root, "perfbench"))
    return _add_second_family(root, bench), root, before


def test_a_second_family_is_new_files_and_new_entries(second_family, bench):
    """A configuration with none of the Transformer's keys, an entry point
    and a traffic mix of its own and per-layer metrics appended after
    PR 24's pass every check of the contract, and the harness finds them,
    with no edit to a file or an entry that is there."""
    new, root, before = second_family
    assert not set(LATER_LM) & {"d_model", "d_inner", "n_head", "n_layer"}
    assert LATER_LM["hidden_size"] % LATER_LM["num_attention_heads"]
    for check in DECLARATIONS.values():
        check(new, root)
    assert [m["name"] for m in new["per_layer"]][-3:] == [
        m["name"] for m in LATER_METRICS]

    cell = harness.Cell("later_lm_saturated", root=root)
    assert cell.chips == 1 and cell.config == LATER_LM
    entry = cell.entry()
    assert entry.__name__ == "perfbench_entry_later_lm"
    assert callable(entry.run) and callable(entry.make_checker)
    assert [m["name"] for m in cell.end_to_end()] == [
        "setup_s", "serve_tokens_per_s"]
    # the three set-up metrics that list no cell, and its own: none of the
    # Transformer cells' metrics, not even ``trace_lower_s``, unless listed
    assert [m["name"] for m in cell.per_layer()] == [
        "build_s", "compile_s", "cache_misses"] + [
        m["name"] for m in LATER_METRICS]
    got = harness.read_layer_metrics(cell, {
        "later": {"later_weights_s": 7.5, "later_admits_per_dispatch": 6},
        "setup": {"program_build": 1.0, "startup_init": 2.0,
                  "reference_check": 0.5},
        "cache": {"compile_seconds": 2.0, "persistent_misses": 0}})
    assert got["build_s"]["value"] == 3.5
    assert got["later_admits_per_dispatch"] == {"value": 6.0,
                                                "unit": "count"}
    # nothing to read is no number: never 0% of a roofline
    assert "later_grouped_experts_roofline" not in got
    # the general generator reads the mix: both lengths are quantiles of
    # their own distributions, in every seed
    assert set(cell.traffic) == set(harness.Cell(
        "serve_base_saturated", root=root).traffic)
    plan = loadgen.make_plan(cell.traffic, 2 ** 31 + 7, 51)
    assert len(plan["trg_len"]) == 4 * 288
    assert sorted(plan["trg_len"]) == sorted(loadgen.draw_lengths(
        cell.traffic["trg_len"], 4 * 288))
    assert 8 <= plan["trg_len"].min() and plan["trg_len"].max() > 400

    # nothing that was there changed: no file, no entry, no other cell
    after = _digest(os.path.join(root, "perfbench"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == LATER_FILES
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(bench[key])] == bench[key]
    for was, now in zip(bench["end_to_end"], new["end_to_end"]):
        assert dict(now, workloads=None) == dict(was, workloads=None)
    for w in bench["workloads"]:
        assert harness.Cell(w["name"], root=root).per_layer() == \
            harness.Cell(w["name"]).per_layer()


# -- a later PR's per-layer metric for a cell that is there, and a further
# -- cell of a family that is there ------------------------------------------

# the end-to-end metric a cell's new per-layer metric moves
MOVED = {"train_big_1chip": "train_tokens_per_s",
         "serve_base_steady": "ttft_p95_ms"}
DECODER_CELLS = ["serve_glm_saturated", "serve_jamba_saturated",
                 "serve_trinity_longctx"]


def _append_metric(root, new, cell):
    """What a ``tracing`` or ``perf_opt`` PR's follow-up adds: a reader
    file and an entry at the END of ``per_layer`` that lists ``cell``."""
    name = "later_%s_count" % cell
    with open(os.path.join(root, "perfbench", "layer_metrics",
                           name + ".py"), "w") as f:
        f.write(LATER_READER % name)
    new["per_layer"].append({
        "name": name, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "serving host plane",
        "moves": MOVED.get(cell, "serve_tokens_per_s"),
        "workloads": [cell]})
    return name


def _append_decoder_cell(root, new):
    """What the next decoder-only ``model_config`` PR adds besides its own
    names: a configuration served through an entry that is there, a cell,
    and the cell's name at the end of the ``workloads`` of every entry the
    three decoder-only cells share."""
    pb = os.path.join(root, "perfbench")
    cfg = dict(harness.load_json(os.path.join(
        pb, "configs", "glm47_flash_6l.json")), name="later_decoder")
    with open(os.path.join(pb, "configs", "later_decoder.json"), "w") as f:
        json.dump(cfg, f)
    new["configs"].append({
        "name": "later_decoder", "source": cfg["source"],
        "file": "perfbench/configs/later_decoder.json",
        "reduced": cfg["reduced"], "why": "a later PR's decoder-only model"})
    new["workloads"].append({
        "name": "later_decoder_saturated", "config": "later_decoder",
        "traffic": "closed_320_clients", "chips": 1,
        "why": "closed loop above the knee: a later PR's cell"})
    shared = set(DECODER_SHARED) | {"trace_lower_s", "serve_tokens_per_s"}
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in shared:
            assert set(DECODER_CELLS) <= set(m["workloads"]), m["name"]
            m["workloads"] = m["workloads"] + ["later_decoder_saturated"]


def _write(root, new):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)


def test_an_addition_at_the_end_breaks_no_declaration_test(copied_tree,
                                                           bench):
    """A per-layer entry appended at the END of ``per_layer`` for each
    cell in turn, then a further decoder-only cell listed under every
    entry the decoder-only cells share: after each step every function
    under tests/perfbench/ that reads BENCHMARK.json's lists holds on the
    copy, and the harness lists the new metric for its cell and for no
    other. A later PR that pins a position or a count fails here."""
    new, root = copied_tree
    for cell in [w["name"] for w in bench["workloads"]]:
        name = _append_metric(root, new, cell)
        _write(root, new)
        for check in DECLARATIONS.values():
            check(new, root)
        for w in bench["workloads"]:
            mine = harness.Cell(w["name"], root=root)
            listed = [m["name"] for m in mine.per_layer()]
            assert (name in listed) == (w["name"] == cell)
            if w["name"] == cell:
                assert listed[-1] == name
                assert mine.per_layer()[-1] == new["per_layer"][-1]
    _append_decoder_cell(root, new)
    _write(root, new)
    for check in DECLARATIONS.values():
        check(new, root)
    later = harness.Cell("later_decoder_saturated", root=root)
    assert later.entry().__name__ == "perfbench_entry_decoder_frontend"
    assert [m["name"] for m in later.per_layer()] == [
        "build_s", "compile_s", "cache_misses", "trace_lower_s"] \
        + DECODER_SHARED
    # nothing that was there changed but the lists the new cell joined
    for key in ("configs", "workloads"):
        assert new[key][:len(bench[key])] == bench[key]
    for was, now in zip(bench["per_layer"] + bench["end_to_end"],
                        new["per_layer"][:len(bench["per_layer"])]
                        + new["end_to_end"]):
        assert dict(now, workloads=None) == dict(was, workloads=None)
        assert now.get("workloads", [])[:len(was.get("workloads", []))] \
            == was.get("workloads", [])


def _pin_the_lists_end(new, root):
    """PR 34's: the LAST entry IS ``sat_cancel_rows_per_dispatch_p50``."""
    assert new["per_layer"][-1] == test_perfbench_cancel_rows.ENTRY


def _pin_a_cells_names(new, root):
    """PR 27's: the GLM cell lists four set-up names and 17 ``glm_``."""
    names = [m["name"] for m in
             harness.Cell("serve_glm_saturated", root=root).per_layer()]
    assert len([n for n in names if n.startswith("glm_")]) == 17
    assert [n for n in names if not n.startswith("glm_")] == [
        "build_s", "compile_s", "cache_misses", "trace_lower_s"]


@pytest.mark.parametrize("pin,cell", [
    (_pin_the_lists_end, "train_big_1chip"),
    (_pin_a_cells_names, "serve_glm_saturated")],
    ids=lambda x: x.__name__[5:] if callable(x) else x)
def test_a_pin_by_position_or_by_count_fails_on_an_addition(pin, cell,
                                                            copied_tree):
    """The two kinds of assertion that held every per-layer addition out
    until PR 38 hold on the tree as it is only while nobody appends."""
    new, root = copied_tree
    _append_metric(root, new, cell)
    _write(root, new)
    with pytest.raises(AssertionError):
        pin(new, root)


def _rewrite_config(root, name, drop=(), **changes):
    path = os.path.join(root, "perfbench", "configs", name + ".json")
    body = dict(harness.load_json(path), **changes)
    for key in drop:
        del body[key]
    with open(path, "w") as f:
        json.dump(body, f)


def _break_reduced_without_published(new, root):
    cut = LATER_LM["reduced"] + ["vocab_size"]
    new["configs"][-1]["reduced"] = cut
    _rewrite_config(root, "later_lm", reduced=cut)


def _break_reduced_key_not_in_file(new, root):
    _rewrite_config(root, "later_lm", drop=["max_position_embeddings"])


def _break_no_precision(new, root):
    _rewrite_config(root, "later_lm", drop=["precision"])


def _break_entry_without_file(new, root):
    _rewrite_config(root, "later_lm", entry="no_such_entry")


def _break_shrunk_transformer(new, root):
    _rewrite_config(root, "transformer_base", d_model=256, n_head=4)


def _break_metric_before_pr24s(new, root):
    new["per_layer"].insert(0, new["per_layer"].pop(
        [m["name"] for m in new["per_layer"]].index("round_ms_p50")))


def _break_sat_metric_on_another_cell(new, root):
    for m in new["per_layer"]:
        if m["name"] == "sat_cancel_ms_p50":
            m["workloads"] = m["workloads"] + ["later_lm_saturated"]


def _break_trace_lower_s_loses_a_cell(new, root):
    for m in new["per_layer"]:
        if m["name"] == "trace_lower_s":
            m["workloads"] = ["later_lm_saturated", "train_big_1chip"]


def _break_metric_without_its_end_to_end(new, root):
    new["per_layer"][-1]["moves"] = "ttft_p95_ms"


@pytest.mark.parametrize("check,fault", [
    ("configs", _break_reduced_without_published),
    ("configs", _break_reduced_key_not_in_file),
    ("configs", _break_no_precision),
    ("configs", _break_entry_without_file),
    ("configs", _break_shrunk_transformer),
    ("new_metrics", _break_metric_before_pr24s),
    ("new_metrics", _break_sat_metric_on_another_cell),
    ("new_metrics", _break_trace_lower_s_loses_a_cell),
    ("metrics", _break_metric_without_its_end_to_end),
], ids=lambda x: x.__name__[7:] if callable(x) else x)
def test_the_contract_refuses(check, fault, second_family):
    """Each check holds on the copy with the second family and fails once
    the named fault is in it."""
    new, root, _before = second_family
    CHECKS[check](new, root)
    fault(new, root)
    with pytest.raises(AssertionError):
        CHECKS[check](new, root)


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
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "train_big_1chip", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
