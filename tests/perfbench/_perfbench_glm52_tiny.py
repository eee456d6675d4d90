"""Tiny sizes for the CPU rehearsal of the sparse-attention latent
decoder's cell: the real entries of BENCHMARK.json with the configuration's
and the traffic's sizes shrunk: ``index_topk`` 8 over pages of 4, so that
a slot passes it within a prompt and again while it decodes, 4 of 16
experts held, prompts on both sides of ``index_topk``. Nothing here is a
device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=12, qk_rope_head_dim=4, qk_head_dim=16, v_head_dim=16,
    head_dim=12, q_lora_rank=24, kv_lora_rank=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=4,
    expert_shard={"of": 16, "first": 0}, num_experts_per_tok=4,
    index_topk=8, index_n_heads=2, index_head_dim=8, vocab_size=512)


def tiny_cell(name="serve_glm52_longctx", root=ROOT, dtype="float32"):
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
                                "index_choice_diff_share": 1e-3,
                                "index_choice_margin_max": 1e-3})
    traffic = dict(real.traffic)
    traffic.update(clients=8, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=10, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
