"""Tiny sizes for the CPU rehearsal of the shortcut decoder's cell: the
real entries of BENCHMARK.json with the configuration's and the traffic's
sizes shrunk: two layers of two latent-attention blocks (4 heads of 8+4
beside values of 8), 2 of 8 real experts held beside 4 identities, top 3,
pages of 4 rows, prompts that end inside a bucket. Nothing here is a
device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=16, kv_lora_rank=8,
    ffn_hidden_size=128, expert_ffn_hidden_size=24, n_routed_experts=2,
    expert_shard={"of": 8, "first": 2}, zero_expert_num=4, moe_topk=3,
    num_layers=2, vocab_size=512)


def tiny_cell(name="serve_longcat_agentic", root=ROOT, dtype="float32"):
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
                                "expert_choice_margin_max": 1e-4})
    traffic = dict(real.traffic)
    traffic.update(clients=8, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=10, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
