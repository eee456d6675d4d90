"""Tiny sizes for the CPU rehearsals of the benchmark's cells: the real
cell entries of BENCHMARK.json with the configuration's and the traffic's
sizes shrunk. Nothing here is a device number."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, run  # noqa: E402

TINY_MODEL = dict(n_layer=2, n_head=2, d_model=32, d_inner=64,
                  src_vocab_size=97, trg_vocab_size=97)


def tiny_cell(name, root=ROOT):
    real = harness.Cell(name, root=root)
    cfg = dict(real.config, **TINY_MODEL)
    traffic = dict(real.traffic)
    if traffic["kind"] == "train":
        # one batch and no dropout: the loss falls from the first step to
        # the second, however few steps a loaded machine fits into 2 s
        cfg.update(max_length=16, dropout=0.0)
        traffic.update(batch_per_chip=2, batches=1, trace_steps=2)
    else:
        cfg["max_length"] = 32
        cfg["pool"] = dict(cfg["pool"], num_slots=8, page_size=8)
        # the CPU computes float32 products exactly, which is NOT the
        # configuration's stated precision (one bfloat16 pass): a CPU
        # rehearsal sits that rounding away from the judged reference
        cfg["check"] = dict(cfg["check"], positions=8,
                            src_len_ranges=[[2, 8], [8, 20]],
                            limits={"logit_rel_l2": 0.05})
        traffic.update(rate_rps=12.0, ramp_s=1.0, drain_s=5.0, trace_s=0.5,
                       stagger_s=0.3,
                       clients=12 if traffic["loop"] == "closed" else 16)
        traffic["src_len"] = dict(traffic["src_len"], median=6, min=2,
                                  max=14)
        traffic["trg_len"] = dict(traffic["trg_len"], max=24)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)


def rehearse(name, tmp_path, trace=0, seconds=2.0, seed=2 ** 31 + 11,
             root=ROOT):
    """Run the cell through run.py's own ``execute`` on CPU devices and
    return (the parsed LAST line of its output, the cell)."""
    import jax

    cell = tiny_cell(name, root=root)
    cell.peaks = {"chips": [{"device_kind": jax.devices()[0].device_kind,
                             "bf16_flops_per_s": 1e12,
                             "hbm_bytes_per_s": 1e11}]}
    run.execute(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                t_start=time.perf_counter(), out_dir=str(tmp_path))
    return cell


def check_line(line, cell, trace):
    """The contract's last line: keys, metric names and units."""
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == cell.chips
    declared = cell.per_layer() if trace else cell.end_to_end()
    units = {m["name"]: m["unit"] for m in declared}
    assert line["metrics"], "no metric on the line"
    for name, m in line["metrics"].items():
        assert name in units, "metric %r is not this cell's" % name
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(line["metrics"]) == set(units)
        assert line["metrics"]["setup_s"]["value"] > 0


# -- what a cell's declaration test asks of BENCHMARK.json -------------------

# the host-plane and device readers every ``DecoderOnlySession`` cell lists
# (they read ``records["serve"]`` as every decoder-only entry fills it)
DECODER_SHARED = [
    "glm_prefill_prompts_per_dispatch_p50", "glm_round_host_ms_p50",
    "glm_device_idle_share", "glm_loadgen_late_p99_ms",
    "glm_admit_self_ms_p50", "glm_cancel_ms_p50", "glm_handoff_ms_p50",
    "glm_worker_offcpu_share", "glm_exec_host_ms_per_dispatch",
    "glm_idle_unattributed_share"]
SETUP = ["build_s", "compile_s", "cache_misses", "trace_lower_s"]


def check_cell_declares(bench, root, cell, names, bare):
    """Set-up's four and every name of ``names`` are entries of ``bench``
    that ``cell`` reports, and the reader of each of ``names`` under
    ``root`` reads None on each of the ``bare`` records (nothing to read
    is no number). By NAME: what else the cell lists, and who else lists
    these entries, is a later PR's."""
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in SETUP + names:
        assert name in declared, "%s declares no %s" % (cell, name)
        assert cell in declared[name].get("workloads", [cell]), (cell, name)
    for name in names:
        reader = harness.load_module(os.path.join(
            root, "perfbench", "layer_metrics", name + ".py"), name)
        for records in bare:
            assert reader.read(records) is None, name
