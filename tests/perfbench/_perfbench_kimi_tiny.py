"""Tiny sizes for the CPU rehearsal of the linear / latent decoder's cell:
the real entries of BENCHMARK.json with the configuration's and the
traffic's sizes shrunk: layers 1, 2, 3 and 5 linear with 4 heads of 16,
layer 4 latent (4 heads of 16 + 8 over a row of 32 + 8), a leading dense
layer, 4 of 16 experts held, pages of 4 rows, prompts that end inside a
chunk. Nothing here is a device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=16, num_heads=4,
        kda_layers=[1, 2, 3, 5], full_attn_layers=[4]),
    intermediate_size=96, moe_intermediate_size=32, num_experts=4,
    expert_shard={"of": 16, "first": 4}, num_experts_per_token=4,
    vocab_size=512)


def tiny_cell(name="serve_kimi_reasoning", root=ROOT, dtype="float32"):
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
