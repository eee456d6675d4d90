"""The plain reference against models/transformer.py at a small size, in
float32 on the CPU: loss and the three gradients agree; the fp8 control
does not."""

import pytest

from _perfbench_tiny import tiny_cell


@pytest.fixture(scope="module")
def checker():
    import paddle_tpu as fluid

    from perfbench import train_common

    cfg = dict(tiny_cell("train_big_1chip").config, amp=None)
    return train_common.Checker(cfg, fluid.CPUPlace())


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_reference_agrees_with_the_program(checker, seed):
    n = checker.numbers(seed)
    assert n["loss_rel"] < 1e-5, n
    assert n["grad_rel_l2"] < 1e-3, n
    assert set(n["grads"]) == {"src_emb", "enc_0_mha_q.w_0",
                               "dec_1_ffn_fc2.w_0"}


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_fp8_control_is_not_correct(checker, seed):
    """The reference itself with fp8 matrix-product operands, the step a
    later PR could be tempted by, fails the cell's own limit."""
    from perfbench import harness, train_common

    limits = harness.Cell("train_big_1chip").config["check"]["limits"]
    control = checker.control_numbers(seed)
    assert control["grad_rel_l2"] > 3 * checker.numbers(seed)["grad_rel_l2"]
    assert control["grad_rel_l2"] > limits["grad_rel_l2"]
    assert not train_common.verdict(control, limits)


def test_weights_are_the_seeds_alone():
    import numpy as np

    from perfbench import weights

    cfg = tiny_cell("train_big_1chip").config
    _t, a = weights.make(cfg, 2 ** 31 + 9)
    _t, b = weights.make(cfg, 2 ** 31 + 9)
    _t, c = weights.make(cfg, 9)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["src_emb"], c["src_emb"])
    assert "dec_1_cmha_k.w_0" in a and "proj_logits.w_1" in a
