"""Tiny sizes for the CPU rehearsal of the Gated DeltaNet / multi-head
decoder's cell: the real entries of BENCHMARK.json with the
configuration's and the traffic's sizes shrunk: 4 layers (three linear
with 6 heads of 24 keys beside 64 values, two heads a tile of the state,
then multi-head attention of 4 heads of 16), SwiGLU of 96, pages of 4 rows,
prompts that end inside a chunk. Nothing here is a device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=6, linear_num_value_heads=6,
    linear_key_head_dim=24, linear_value_head_dim=64,
    intermediate_size=96, vocab_size=512)


def tiny_cell(name="serve_olmo_evalgen", root=ROOT, dtype="float32"):
    real = harness.Cell(name, root=root)
    cfg = dict(real.config, dtype=dtype, **TINY_MODEL)
    cfg["pool"] = dict(num_slots=6, max_prompt=32, max_new_tokens=24,
                       page_size=4, tokens_per_dispatch=2,
                       prefill_buckets=[8, 16, 32], prefill_token_budget=64,
                       prefill_rungs=True, admit_token_budget=64)
    # float32 on the CPU sits on the reference; the real limits are the
    # chip's alone
    cfg["check"] = dict(cfg["check"], positions=8,
                        prompt_len_ranges=[[3, 8], [20, 32]],
                        limits={"logit_rel_l2": 1e-4,
                                "state_rel_l2": 1e-4,
                                "state_bf16_grid_share": 1e-2})
    traffic = dict(real.traffic)
    traffic.update(clients=8, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=10, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
