"""Tiny sizes for the CPU rehearsal of the window/full-attention decoder's
cell: the real entries of BENCHMARK.json with the configuration's and the
traffic's sizes shrunk: a window of 8 over pages of 4, so that a slot's
ring gives pages back many times in a stream, and prompts on both sides of
the window. Nothing here is a device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, sliding_window=8, vocab_size=512)


def tiny_cell(name="serve_trinity_longctx", root=ROOT, dtype="float32"):
    real = harness.Cell(name, root=root)
    cfg = dict(real.config, dtype=dtype, **TINY_MODEL)
    cfg["pool"] = dict(num_slots=12, max_prompt=32, max_new_tokens=24,
                       page_size=4, tokens_per_dispatch=2,
                       prefill_buckets=[8, 16, 32], prefill_token_budget=64,
                       admit_token_budget=128)
    # float32 on the CPU sits on the reference; the real limits are the
    # chip's alone
    cfg["check"] = dict(cfg["check"], positions=8,
                        prompt_len_ranges=[[3, 8], [20, 32]],
                        limits={"logit_rel_l2": 1e-4,
                                "expert_choice_diff_share": 1e-3,
                                "expert_choice_margin_max": 1e-4})
    traffic = dict(real.traffic)
    traffic.update(clients=16, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=10, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
