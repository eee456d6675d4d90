"""Tiny sizes for the CPU rehearsal of the hybrid state-space decoder's
cell: the real entries of BENCHMARK.json with the configuration's and the
traffic's sizes shrunk: two periods of the layer pattern, so that both
kinds of layer and both offsets occur. Nothing here is a device number."""

from _perfbench_tiny import ROOT

from perfbench import harness

TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=160, mamba_d_state=4, mamba_dt_rank=8,
    attn_layer_period=3, attn_layer_offset=1, num_hidden_layers=6,
    vocab_size=512)


def tiny_cell(name="serve_jamba_saturated", root=ROOT, dtype="float32"):
    real = harness.Cell(name, root=root)
    cfg = dict(real.config, dtype=dtype, **TINY_MODEL)
    cfg["pool"] = dict(num_slots=12, max_prompt=32, max_new_tokens=24,
                       page_size=8, tokens_per_dispatch=2,
                       prefill_buckets=[8, 16, 32], prefill_token_budget=64)
    # float32 on the CPU sits on the reference; the real limits are the
    # chip's alone
    cfg["check"] = dict(cfg["check"], positions=8,
                        prompt_len_ranges=[[3, 8], [8, 32]],
                        limits={"logit_rel_l2": 1e-4, "state_rel_l2": 1e-4,
                                "state_slow_rel_l2": 1e-4})
    traffic = dict(real.traffic)
    traffic.update(clients=16, ramp_s=1.0, drain_s=8.0, trace_s=0.5,
                   stagger_s=0.3)
    traffic["src_len"] = dict(traffic["src_len"], median=8, min=2, max=32)
    traffic["trg_len"] = dict(traffic["trg_len"], median=8, min=2, max=20)
    return harness.Cell(name, root=root, config=cfg, traffic=traffic)
