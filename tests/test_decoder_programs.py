"""The decoder-only serving programs, op for op and name for name.

``models/decoder_programs.py`` writes the programs' frame once and the seven
family files fill it; ``tests/golden/decoder_programs.json`` holds
``core.fingerprint.program_fingerprint`` of every program of the nine test
descriptions (``DESC`` of ``test_latent_moe_decoder``,
``test_sparse_latent_decoder``, ``test_hybrid_ssm_decoder``,
``test_windowed_moe_decoder``, ``test_linear_attn_decoder``,
``test_ssd_moe_decoder``, ``test_shortcut_moe_decoder``,
``test_linear_latent_decoder``, ``test_gated_delta_decoder``, at the
geometry those files' sessions use): ``init``, ``step``, ``step`` with
``probe_rows=2`` and every rung of every bucket's prefill. They were
recorded at the commit BEFORE the frame was written once (the sixth to
the ninth with the PR that brought each; a rung of B rows
that a builder of that commit did not take is the program it built for a
budget of B rows), so a case fails at any commit that adds, drops or
reorders an op, renames a variable or changes an attribute: the fingerprint
is the executable cache's key, and a changed one is a fresh compile in
every cell that serves the family.

To change a program ON PURPOSE: make the change, run

    JAX_PLATFORMS=cpu python -m tests.test_decoder_programs

from the root of the repo, which rewrites the golden file from the working
tree, and commit it with the change (the diff of the JSON names the
programs that moved).
"""

import functools
import importlib
import json
import os

import pytest

from paddle_tpu.core.fingerprint import program_fingerprint
from paddle_tpu.models.decoder_programs import builder_for
from paddle_tpu.serving.server import ServingError

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "decoder_programs.json")
# the test file whose DESC it is -> its sessions' slots
FAMILIES = {"test_latent_moe_decoder": 4, "test_sparse_latent_decoder": 4,
            "test_hybrid_ssm_decoder": 6, "test_windowed_moe_decoder": 4,
            "test_linear_attn_decoder": 6, "test_ssd_moe_decoder": 6,
            "test_shortcut_moe_decoder": 4, "test_linear_latent_decoder": 6,
            "test_gated_delta_decoder": 6}


@functools.lru_cache(maxsize=None)
def fingerprints(module):
    """{program: fingerprint} of ``module.DESC``'s programs at the session
    geometry of the test files: 48 positions in pages of 8 (of ``PS`` where
    a file has one), buckets of a page doubled up to 32 tokens under a
    budget of 64, 2 tokens a dispatch."""
    family = importlib.import_module(module)
    desc, ps = family.DESC, getattr(family, "PS", 8)

    def build(**kw):
        return builder_for(desc)(
            desc, FAMILIES[module], 48, ps,
            [t for t in (4, 8, 16, 32) if t >= ps],
            prefill_token_budget=64, dtype="float32",
            tokens_per_dispatch=2, **kw)

    built = build(prefill_rungs=True)
    progs = {"init": built["init"], "step": built["step"],
             "step/probe2": build(probe_rows=2)["step"]}
    for bucket, rungs in built["prefill_rungs"].items():
        for rows, prog in rungs.items():
            progs["prefill/%d/%d" % (bucket, rows)] = prog
    return {k: program_fingerprint(p) for k, p in progs.items()}


with open(GOLDEN) as _f:
    WANT = json.load(_f)


@pytest.mark.parametrize(
    "module,program",
    [(m, p) for m in sorted(WANT) for p in sorted(WANT[m])])
def test_program_is_the_recorded_one(module, program):
    got = fingerprints(module)
    assert sorted(got) == sorted(WANT[module])
    assert got[program] == WANT[module][program], (
        "%s of %s.DESC is not the program recorded in %s: an op, a name or "
        "an attribute changed (this file's docstring says how to record a "
        "change made on purpose)" % (program, module, GOLDEN))


def test_every_family_has_its_goldens():
    assert sorted(WANT) == sorted(FAMILIES)
    for module, programs in WANT.items():
        # init, step, step with a probe, and 4 + 3 + 2 rungs; 5 more of
        # the windowed file's bucket of one page of 4
        assert len(programs) == (17 if "windowed" in module else 12)


def test_an_unknown_description_is_refused_with_the_families_named():
    with pytest.raises(ServingError) as err:
        builder_for({"hidden_size": 64, "vocab_size": 512})
    assert str(err.value) == (
        "DecoderOnlySession knows no builder for this description (keys "
        "['hidden_size', 'vocab_size']): it serves a hybrid Mamba-2 decoder "
        "with routed experts (mamba_n_heads), a hybrid state-space "
        "decoder (mamba_d_state), a decoder of two latent-attention blocks "
        "a layer with the expert block on a shortcut and zero-compute "
        "experts (zero_expert_num), a decoder of delta-rule "
        "linear-attention layers beside latent-attention layers "
        "(linear_attn_config with kv_lora_rank), a latent-attention decoder "
        "(kv_lora_rank), a decoder of window and full attention layers "
        "(layer_types with a sliding_window), a decoder of delta-rule "
        "linear-attention and grouped-query attention layers "
        "(linear_attn_config) or a dense decoder of Gated DeltaNet and "
        "multi-head attention layers (linear_key_head_dim)")


def test_the_two_namings_of_the_linear_family_reach_one_builder():
    """A ``kimi_linear`` description has ``kv_lora_rank`` beside its
    ``linear_attn_config``: the row that asks for both stands before the
    latent family's, and ``solar_open2``'s still reaches the same file."""
    import test_linear_attn_decoder as solar
    import test_linear_latent_decoder as kimi
    from paddle_tpu.models import linear_attn_moe_decoder as lad

    assert "kv_lora_rank" in kimi.DESC and "kv_lora_rank" not in solar.DESC
    assert builder_for(kimi.DESC) is builder_for(solar.DESC) \
        is lad.build_linear_attn_moe_decoder


def test_a_mamba2_description_and_a_mamba1_description_part_ways():
    """Both have ``mamba_d_state``; the row that asks for heads stands
    first and the Mamba-1 description still reaches its own builder."""
    import test_hybrid_ssm_decoder as jamba
    import test_ssd_moe_decoder as granite
    from paddle_tpu.models import hybrid_ssm_decoder, ssd_moe_decoder

    assert "mamba_d_state" in granite.DESC and "mamba_d_state" in jamba.DESC
    assert builder_for(granite.DESC) is ssd_moe_decoder.build_ssd_moe_decoder
    assert builder_for(jamba.DESC) \
        is hybrid_ssm_decoder.build_hybrid_ssm_decoder


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    with open(GOLDEN, "w") as out:
        json.dump({m: fingerprints(m) for m in FAMILIES}, out, indent=1,
                  sort_keys=True)
        out.write("\n")
    print("recorded %d programs in %s"
          % (sum(len(fingerprints(m)) for m in FAMILIES), GOLDEN))
