"""Tiny sizes for the CPU rehearsal of the hybrid Mamba-2 decoder's cell:
the real entries of BENCHMARK.json with the configuration's and the
traffic's sizes shrunk: two Mamba-2 layers of 8 heads of 16 on a state of
16, an attention layer and a Mamba-2 layer, 4 of 8 experts held, pages of
4 rows, prompts that end inside a bucket. Nothing here is a device
number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    intermediate_size=32, shared_intermediate_size=48, num_local_experts=4,
    expert_shard={"of": 8, "first": 2}, num_experts_per_tok=2,
    attention_multiplier=1.0 / 16, vocab_size=512)


def tiny_cell(name="serve_granite_sessions", root=ROOT, dtype="float32"):
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
                                "expert_choice_diff_share": 1e-3,
                                "expert_choice_margin_max": 1e-4,
                                "state_rel_l2": 1e-4,
                                "state_bf16_grid_share": 1e-2})
    traffic = dict(real.traffic)
    traffic.update(clients=8, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=10, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
