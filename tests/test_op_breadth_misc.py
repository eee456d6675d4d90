"""Breadth sweep part 2: optimizer update rules, RNN units, random ops,
tensor-array ops, interpolation/conv variants, and detection/metric
utilities that previously had no dedicated test.

Optimizer mirrors are written from the reference update rules
(operators/{adadelta,adagrad,adamax,decayed_adagrad,ftrl,rmsprop,
proximal_adagrad,proximal_gd,lars_momentum}_op.cc), evaluated in numpy
float64 and compared against the op output after one step.
"""

import numpy as np
import pytest

import paddle_tpu as fluid

from op_test import make_grad_test as _shapes, make_op_test as _t


def _run(op_type, inputs, fetch, attrs=None):
    """Build a one-op program and fetch the named outputs."""
    t = _shapes(op_type, inputs, {k: (1,) for k in fetch}, attrs)
    main = t._build()
    exe = fluid.Executor(fluid.CPUPlace())
    return [np.asarray(v) for v in
            exe.run(main, feed=t._feed, fetch_list=list(fetch))]


_RNG = np.random.RandomState


def _opt_inputs(rng, extra=()):
    ins = {
        "Param": rng.randn(3, 4).astype("float32"),
        "Grad": rng.randn(3, 4).astype("float32"),
        "LearningRate": np.asarray([0.05], "float32"),
    }
    for slot in extra:
        ins[slot] = np.abs(rng.randn(3, 4)).astype("float32") * 0.1
    return ins


def test_adadelta_update():
    rng = _RNG(50)
    ins = _opt_inputs(rng, ["AvgSquaredGrad", "AvgSquaredUpdate"])
    del ins["LearningRate"]  # adadelta_op.cc has no LR input
    p, g = ins["Param"].astype("float64"), ins["Grad"].astype("float64")
    asg, asu = (ins["AvgSquaredGrad"].astype("float64"),
                ins["AvgSquaredUpdate"].astype("float64"))
    rho, eps = 0.95, 1e-6
    asg_o = rho * asg + (1 - rho) * g * g
    upd = -np.sqrt((asu + eps) / (asg_o + eps)) * g
    asu_o = rho * asu + (1 - rho) * upd * upd
    _t("adadelta", ins,
       {"ParamOut": p + upd, "AvgSquaredGradOut": asg_o,
        "AvgSquaredUpdateOut": asu_o},
       {"rho": rho, "epsilon": eps}).check_output()


def test_adagrad_update():
    rng = _RNG(51)
    ins = _opt_inputs(rng, ["Moment"])
    p, g, m = (ins[k].astype("float64") for k in ("Param", "Grad", "Moment"))
    lr, eps = 0.05, 1e-6
    m_o = m + g * g
    _t("adagrad", ins,
       {"ParamOut": p - lr * g / (np.sqrt(m_o) + eps), "MomentOut": m_o},
       {"epsilon": eps}).check_output()


def test_adamax_update():
    rng = _RNG(52)
    ins = _opt_inputs(rng, ["Moment", "InfNorm"])
    ins["Beta1Pow"] = np.asarray([0.9], "float32")
    p, g, m, inf = (ins[k].astype("float64")
                    for k in ("Param", "Grad", "Moment", "InfNorm"))
    lr, b1, b2, eps = 0.05, 0.9, 0.999, 1e-8
    m_o = b1 * m + (1 - b1) * g
    inf_o = np.maximum(b2 * inf, np.abs(g))
    lr_t = lr / (1 - 0.9)
    _t("adamax", ins,
       {"ParamOut": p - lr_t * m_o / (inf_o + eps),
        "MomentOut": m_o, "InfNormOut": inf_o},
       {"beta1": b1, "beta2": b2, "epsilon": eps}).check_output()


def test_decayed_adagrad_update():
    rng = _RNG(53)
    ins = _opt_inputs(rng, ["Moment"])
    p, g, m = (ins[k].astype("float64") for k in ("Param", "Grad", "Moment"))
    lr, decay, eps = 0.05, 0.95, 1e-6
    m_o = decay * m + (1 - decay) * g * g
    _t("decayed_adagrad", ins,
       {"ParamOut": p - lr * g / (np.sqrt(m_o) + eps), "MomentOut": m_o},
       {"decay": decay, "epsilon": eps}).check_output()


def test_ftrl_update():
    rng = _RNG(54)
    ins = _opt_inputs(rng, ["SquaredAccumulator", "LinearAccumulator"])
    p, g = ins["Param"].astype("float64"), ins["Grad"].astype("float64")
    sq = ins["SquaredAccumulator"].astype("float64")
    lin = ins["LinearAccumulator"].astype("float64")
    lr, l1, l2, power = 0.05, 0.1, 0.1, -0.5
    new_sq = sq + g * g
    sigma = (new_sq ** -power - sq ** -power) / lr
    lin_o = lin + g - sigma * p
    x = l1 * np.sign(lin_o) - lin_o
    y = new_sq ** -power / lr + 2 * l2
    p_o = np.where(np.abs(lin_o) > l1, x / y, 0.0)
    _t("ftrl", ins,
       {"ParamOut": p_o, "SquaredAccumOut": new_sq, "LinearAccumOut": lin_o},
       {"l1": l1, "l2": l2, "lr_power": power}).check_output()


@pytest.mark.parametrize("centered", [False, True], ids=["plain", "centered"])
def test_rmsprop_update(centered):
    rng = _RNG(55)
    ins = _opt_inputs(rng, ["MeanSquare", "MeanGrad", "Moment"])
    p, g = ins["Param"].astype("float64"), ins["Grad"].astype("float64")
    ms = ins["MeanSquare"].astype("float64")
    mg = ins["MeanGrad"].astype("float64")
    mom = ins["Moment"].astype("float64")
    lr, rho, eps, mu = 0.05, 0.9, 1e-10, 0.9
    ms_o = rho * ms + (1 - rho) * g * g
    outs = {"MeanSquareOut": ms_o}
    if centered:
        mg_o = rho * mg + (1 - rho) * g
        denom = ms_o - mg_o * mg_o + eps
        outs["MeanGradOut"] = mg_o
    else:
        denom = ms_o + eps
    mom_o = mu * mom + lr * g / np.sqrt(denom)
    outs.update({"ParamOut": p - mom_o, "MomentOut": mom_o})
    _t("rmsprop", ins, outs,
       {"decay": rho, "epsilon": eps, "momentum": mu,
        "centered": centered}).check_output()


def test_proximal_adagrad_update():
    rng = _RNG(56)
    ins = _opt_inputs(rng, ["Moment"])
    p, g, m = (ins[k].astype("float64") for k in ("Param", "Grad", "Moment"))
    lr, l1, l2 = 0.05, 0.1, 0.05
    m_o = m + g * g
    lr_t = lr / np.sqrt(m_o)
    prox = p - lr_t * g
    p_o = np.sign(prox) * np.maximum(np.abs(prox) - lr_t * l1, 0) / \
        (1 + lr_t * l2)
    _t("proximal_adagrad", ins, {"ParamOut": p_o, "MomentOut": m_o},
       {"l1": l1, "l2": l2}).check_output()


def test_proximal_gd_update():
    rng = _RNG(57)
    ins = _opt_inputs(rng)
    p, g = ins["Param"].astype("float64"), ins["Grad"].astype("float64")
    lr, l1, l2 = 0.05, 0.1, 0.05
    prox = p - lr * g
    p_o = np.sign(prox) * np.maximum(np.abs(prox) - lr * l1, 0) / \
        (1 + lr * l2)
    _t("proximal_gd", ins, {"ParamOut": p_o},
       {"l1": l1, "l2": l2}).check_output()


def test_lars_momentum_update():
    rng = _RNG(58)
    ins = _opt_inputs(rng, ["Velocity"])
    p, g, v = (ins[k].astype("float64")
               for k in ("Param", "Grad", "Velocity"))
    lr, mu, coeff, wd = 0.05, 0.9, 0.001, 0.0005
    p_n = np.sqrt(np.sum(p * p))
    g_n = np.sqrt(np.sum(g * g))
    local_lr = lr * coeff * p_n / (g_n + wd * p_n + 1e-12)
    v_o = mu * v + local_lr * (g + wd * p)
    _t("lars_momentum", ins, {"ParamOut": p - v_o, "VelocityOut": v_o},
       {"mu": mu, "lars_coeff": coeff,
        "lars_weight_decay": wd}).check_output()


# --- RNN building blocks -------------------------------------------------
def test_lstm_unit_output_and_grad():
    rng = _RNG(60)
    B, D = 3, 4
    x = rng.randn(B, 4 * D).astype("float32")
    c_prev = rng.randn(B, D).astype("float32")
    fb = 1.0
    x64, c64 = x.astype("float64"), c_prev.astype("float64")

    def sig(v):
        return 1.0 / (1.0 + np.exp(-v))

    i = sig(x64[:, :D])
    f = sig(x64[:, D:2 * D] + fb)
    g = np.tanh(x64[:, 2 * D:3 * D])
    o = sig(x64[:, 3 * D:])
    c = f * c64 + i * g
    h = o * np.tanh(c)
    t = _t("lstm_unit", {"X": x, "C_prev": c_prev}, {"C": c, "H": h},
           {"forget_bias": fb})
    t.check_output()
    _shapes("lstm_unit", {"X": x, "C_prev": c_prev},
            {"C": (B, D), "H": (B, D)},
            {"forget_bias": fb}).check_grad(["X", "C_prev"], "H")


def test_gru_unit_output_and_grad():
    rng = _RNG(61)
    B, D = 3, 4
    x = rng.randn(B, 3 * D).astype("float32")
    h_prev = rng.randn(B, D).astype("float32")
    w = (0.5 * rng.randn(D, 3 * D)).astype("float32")
    bias = (0.1 * rng.randn(1, 3 * D)).astype("float32")
    x64, h64, w64, b64 = (a.astype("float64") for a in (x, h_prev, w,
                                                        bias.ravel()))

    def sig(v):
        return 1.0 / (1.0 + np.exp(-v))

    g = x64[:, :2 * D] + h64 @ w64[:, :2 * D] + b64[:2 * D]
    u = sig(g[:, :D])
    r = sig(g[:, D:])
    c = np.tanh(x64[:, 2 * D:] + (r * h64) @ w64[:, 2 * D:] + b64[2 * D:])
    h = u * h64 + (1 - u) * c
    t = _t("gru_unit",
           {"Input": x, "HiddenPrev": h_prev, "Weight": w, "Bias": bias},
           {"Hidden": h})
    t.check_output()
    _shapes("gru_unit",
            {"Input": x, "HiddenPrev": h_prev, "Weight": w, "Bias": bias},
            {"Hidden": (B, D)}).check_grad(
        ["Input", "HiddenPrev", "Weight"], "Hidden",
        max_relative_error=1e-2)


def test_dynamic_lstmp_shapes_and_grad():
    rng = _RNG(62)
    B, T, D, P = 2, 5, 4, 3
    ins = {
        "Input": rng.randn(B, T, 4 * D).astype("float32") * 0.5,
        "Weight": (0.3 * rng.randn(P, 4 * D)).astype("float32"),
        "ProjWeight": (0.5 * rng.randn(D, P)).astype("float32"),
        "Bias": (0.1 * rng.randn(1, 4 * D)).astype("float32"),
        "Length": np.asarray([T, T - 2], "int32"),
    }
    t = _shapes("dynamic_lstmp", ins,
                {"Projection": (B, T, P), "Cell": (B, T, D)},
                {"use_peepholes": False})
    (proj,) = _run("dynamic_lstmp", ins, ["Projection"],
                   {"use_peepholes": False})
    assert proj.shape == (B, T, P) and np.isfinite(proj).all()
    # padded steps beyond Length carry state: projection frozen after t=3
    np.testing.assert_allclose(proj[1, T - 2], proj[1, T - 1], rtol=1e-5)
    # fd through the T-step recurrence accumulates cancellation noise
    t.check_grad(["Input", "Weight", "ProjWeight"], "Projection",
                 max_relative_error=3e-2)


def test_hierarchical_sigmoid_grad():
    rng = _RNG(63)
    B, D, K = 4, 5, 4
    ins = {
        "X": rng.randn(B, D).astype("float32"),
        "W": (0.5 * rng.randn(K - 1, D)).astype("float32"),
        "Label": rng.randint(0, K, (B, 1)).astype("int64"),
        "Bias": (0.1 * rng.randn(1, K - 1)).astype("float32"),
    }
    t = _shapes("hierarchical_sigmoid", ins, {"Out": (B, 1)},
                {"num_classes": K})
    t.check_grad(["X", "W"], "Out", max_relative_error=1e-2)


# --- random ops ----------------------------------------------------------
def test_gaussian_random_statistics():
    (out,) = _run("gaussian_random", {}, ["Out"],
                  {"shape": [200, 100], "mean": 1.0, "std": 2.0, "seed": 7,
                   "dtype": "float32"})
    assert out.shape == (200, 100)
    assert abs(out.mean() - 1.0) < 0.05
    assert abs(out.std() - 2.0) < 0.05


def test_uniform_random_statistics():
    (out,) = _run("uniform_random", {}, ["Out"],
                  {"shape": [200, 100], "min": -2.0, "max": 4.0, "seed": 7,
                   "dtype": "float32"})
    assert out.shape == (200, 100)
    assert out.min() >= -2.0 and out.max() <= 4.0
    assert abs(out.mean() - 1.0) < 0.1


def test_truncated_gaussian_random_statistics():
    (out,) = _run("truncated_gaussian_random", {}, ["Out"],
                  {"shape": [200, 100], "mean": 0.0, "std": 1.0, "seed": 7,
                   "dtype": "float32"})
    # truncated at two standard deviations (reference
    # truncated_gaussian_random_op.cc contract)
    assert np.abs(out).max() <= 2.0 + 1e-5
    assert abs(out.mean()) < 0.05


def test_sampling_id_distribution():
    rng = _RNG(64)
    probs = np.tile(np.asarray([[0.7, 0.2, 0.1, 0.0]], "float32"),
                    (512, 1))
    (ids,) = _run("sampling_id", {"X": probs}, ["Out"], {"seed": 9})
    assert ids.shape[0] == 512
    assert set(np.unique(ids)) <= {0, 1, 2}
    frac0 = float(np.mean(ids == 0))
    assert 0.6 < frac0 < 0.8  # matches the 0.7 row mass


# --- tensor arrays -------------------------------------------------------
def test_tensor_array_write_read_length():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        i0 = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        i1 = fluid.layers.fill_constant(shape=[1], dtype="int64", value=1)
        arr = fluid.layers.array_write(x, i0)
        fluid.layers.array_write(x * 2.0, i1, array=arr)
        n = fluid.layers.array_length(arr)
        back = fluid.layers.array_read(arr, i1)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = _RNG(65).randn(2, 4).astype("float32")
    n_v, back_v = exe.run(main, feed={"x": xv}, fetch_list=[n, back])
    assert int(np.ravel(n_v)[0]) == 2
    np.testing.assert_allclose(back_v, xv * 2.0, rtol=1e-6)


def test_lod_tensor_to_array_round_trip():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3, 4], lod_level=1)
        lens = fluid.layers.data("x_len", [1], dtype="int64")
        table = fluid.layers.lod_rank_table(lengths=lens)
        arr = fluid.layers.lod_tensor_to_array(x, table)
        back = fluid.layers.array_to_lod_tensor(arr, table)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = _RNG(66).randn(2, 3, 4).astype("float32")
    lv = np.asarray([[3], [1]], "int64")
    (out,) = exe.run(main, feed={"x": xv, "x_len": lv}, fetch_list=[back])
    np.testing.assert_allclose(out, xv, rtol=1e-6)


# --- sequence ------------------------------------------------------------
def test_sequence_pad_output_and_grad():
    rng = _RNG(67)
    B, T, D, PT = 2, 3, 4, 5
    x = rng.randn(B, T, D).astype("float32")
    lens = np.asarray([3, 1], "int64")
    pad = np.asarray([0.25], "float32")
    expect = np.full((B, PT, D), 0.25, "float32")
    for b in range(B):
        expect[b, :lens[b]] = x[b, :lens[b]]
    t = _t("sequence_pad", {"X": x, "PadValue": pad, "Length": lens},
           {"Out": expect}, {"padded_length": PT})
    t.check_output()
    _shapes("sequence_pad", {"X": x, "PadValue": pad, "Length": lens},
            {"Out": (B, PT, D)}, {"padded_length": PT}).check_grad(
        ["X"], "Out")


def test_sequence_reverse_output_and_grad():
    rng = _RNG(68)
    x = rng.randn(2, 4, 3).astype("float32")
    lens = np.asarray([4, 2], "int64")
    expect = x.copy()
    expect[0] = x[0, ::-1]
    expect[1, :2] = x[1, 1::-1]
    t = _t("sequence_reverse", {"X": x, "Length": lens}, {"Y": expect})
    t.check_output()
    _shapes("sequence_reverse", {"X": x, "Length": lens},
            {"Y": (2, 4, 3)}).check_grad(["X"], "Y")


def test_sequence_scatter_output_and_grad():
    rng = _RNG(69)
    x = rng.randn(2, 5).astype("float32")
    ids = np.asarray([[0, 3], [1, 4]], "int32")
    upd = rng.randn(2, 2).astype("float32")
    expect = x.copy()
    for b in range(2):
        for k in range(2):
            expect[b, ids[b, k]] += upd[b, k]
    t = _t("sequence_scatter", {"X": x, "Ids": ids, "Updates": upd},
           {"Out": expect})
    t.check_output()
    _shapes("sequence_scatter", {"X": x, "Ids": ids, "Updates": upd},
            {"Out": (2, 5)}).check_grad(["X", "Updates"], "Out")


# --- interpolation / conv variants --------------------------------------
def test_nearest_interp_output_and_grad():
    rng = _RNG(70)
    x = rng.randn(1, 2, 3, 3).astype("float32")
    t = _shapes("nearest_interp", {"X": x}, {"Out": (1, 2, 6, 6)},
                {"out_h": 6, "out_w": 6})
    (out,) = _run("nearest_interp", {"X": x}, ["Out"],
                  {"out_h": 6, "out_w": 6})
    assert out.shape == (1, 2, 6, 6)
    # every output value is one of the input values (nearest semantics)
    assert np.isin(np.round(out, 5), np.round(x, 5)).all()
    t.check_grad(["X"], "Out")


def test_bilinear_interp_output_and_grad():
    rng = _RNG(71)
    x = rng.randn(1, 2, 3, 3).astype("float32")
    t = _shapes("bilinear_interp", {"X": x}, {"Out": (1, 2, 6, 6)},
                {"out_h": 6, "out_w": 6})
    (out,) = _run("bilinear_interp", {"X": x}, ["Out"],
                  {"out_h": 6, "out_w": 6})
    assert out.shape == (1, 2, 6, 6)
    # interpolation stays inside the input's range
    assert out.min() >= x.min() - 1e-5 and out.max() <= x.max() + 1e-5
    t.check_grad(["X"], "Out", max_relative_error=1e-2)


def test_conv3d_grad():
    rng = _RNG(72)
    x = rng.randn(1, 2, 4, 4, 4).astype("float32")
    w = (0.3 * rng.randn(3, 2, 2, 2, 2)).astype("float32")
    t = _shapes("conv3d", {"Input": x, "Filter": w},
                {"Output": (1, 3, 3, 3, 3)},
                {"strides": [1, 1, 1], "paddings": [0, 0, 0],
                 "dilations": [1, 1, 1], "groups": 1})
    t.check_grad(["Input", "Filter"], "Output", max_relative_error=2e-2)


def test_depthwise_conv2d_output_and_grad():
    rng = _RNG(73)
    x = rng.randn(1, 3, 5, 5).astype("float32")
    w = (0.3 * rng.randn(3, 1, 3, 3)).astype("float32")
    attrs = {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
             "groups": 3}
    expect = np.zeros((1, 3, 3, 3), "float64")
    for c in range(3):
        for i in range(3):
            for j in range(3):
                expect[0, c, i, j] = np.sum(
                    x[0, c, i:i + 3, j:j + 3].astype("float64")
                    * w[c, 0].astype("float64"))
    t = _t("depthwise_conv2d", {"Input": x, "Filter": w},
           {"Output": expect}, attrs)
    t.check_output(atol=1e-4, rtol=1e-3)
    _shapes("depthwise_conv2d", {"Input": x, "Filter": w},
            {"Output": (1, 3, 3, 3)}, attrs).check_grad(
        ["Input", "Filter"], "Output", max_relative_error=1e-2)


# --- detection / metric utilities ---------------------------------------
def test_iou_similarity_output():
    x = np.asarray([[0, 0, 2, 2], [1, 1, 3, 3]], "float32")
    y = np.asarray([[0, 0, 2, 2], [2, 2, 4, 4], [10, 10, 11, 11]],
                   "float32")

    def iou(a, b):
        ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
        iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
        inter = ix * iy
        ua = ((a[2] - a[0]) * (a[3] - a[1])
              + (b[2] - b[0]) * (b[3] - b[1]) - inter)
        return inter / ua if ua > 0 else 0.0

    expect = np.asarray([[iou(a, b) for b in y] for a in x], "float32")
    _t("iou_similarity", {"X": x, "Y": y}, {"Out": expect}).check_output()


def test_box_coder_encode_output():
    prior = np.asarray([[0, 0, 2, 2], [1, 1, 4, 5]], "float32")
    pvar = np.tile(np.asarray([[0.1, 0.1, 0.2, 0.2]], "float32"), (2, 1))
    target = np.asarray([[0, 0, 2, 2], [0.5, 0.5, 3, 3.5]], "float32")
    pw = prior[:, 2] - prior[:, 0]
    ph = prior[:, 3] - prior[:, 1]
    pcx = prior[:, 0] + pw / 2
    pcy = prior[:, 1] + ph / 2
    tw = target[:, 2] - target[:, 0]
    th = target[:, 3] - target[:, 1]
    tcx = target[:, 0] + tw / 2
    tcy = target[:, 1] + th / 2
    expect = np.stack([
        (tcx[:, None] - pcx[None, :]) / pw[None, :] / pvar[None, :, 0],
        (tcy[:, None] - pcy[None, :]) / ph[None, :] / pvar[None, :, 1],
        np.log(tw[:, None] / pw[None, :]) / pvar[None, :, 2],
        np.log(th[:, None] / ph[None, :]) / pvar[None, :, 3],
    ], axis=-1).astype("float32")
    _t("box_coder",
       {"PriorBox": prior, "PriorBoxVar": pvar, "TargetBox": target},
       {"OutputBox": expect},
       {"code_type": "encode_center_size"}).check_output(
        atol=1e-5, rtol=1e-4)


def test_ctc_align_output():
    # path [blank a a blank b b] -> [a b]; merge_repeated + blank removal
    x = np.asarray([[0, 1, 1, 0, 2, 2], [3, 3, 0, 0, 0, 1]], "int32")
    lens = np.asarray([6, 3], "int32")
    out, n = _run("ctc_align", {"Input": x, "InputLength": lens},
                  ["Output", "OutputLength"], {"blank": 0})
    n = np.ravel(n)
    assert list(out[0][:n[0]]) == [1, 2]
    assert list(out[1][:n[1]]) == [3]  # steps past InputLength ignored
    assert (out[0][n[0]:] == 0).all()


def test_auc_perfect_separation():
    n_t = 200
    preds = np.asarray([[0.1, 0.9]] * 8 + [[0.9, 0.1]] * 8, "float32")
    labels = np.asarray([[1]] * 8 + [[0]] * 8, "int64")
    zeros = np.zeros((n_t,), "int64")
    auc, sp, sn = _run(
        "auc",
        {"Predict": preds, "Label": labels, "StatPos": zeros,
         "StatNeg": zeros},
        ["AUC", "StatPosOut", "StatNegOut"],
        {"curve": "ROC", "num_thresholds": n_t})
    assert float(np.ravel(auc)[0]) > 0.99
    assert int(sp.sum()) == 8 and int(sn.sum()) == 8


def test_prior_box_output_shapes_and_ranges():
    feat = np.zeros((1, 4, 2, 2), "float32")
    img = np.zeros((1, 3, 8, 8), "float32")
    boxes, variances = _run(
        "prior_box", {"Input": feat, "Image": img}, ["Boxes", "Variances"],
        {"min_sizes": [4.0], "max_sizes": [], "aspect_ratios": [1.0],
         "variances": [0.1, 0.1, 0.2, 0.2], "flip": False, "clip": True,
         "step_w": 0.0, "step_h": 0.0, "offset": 0.5})
    assert boxes.shape[-1] == 4 and variances.shape[-1] == 4
    assert boxes.min() >= 0.0 and boxes.max() <= 1.0  # clip=True
    # centers sit at (i + 0.5) * step / img: distinct per cell
    flat = boxes.reshape(-1, 4)
    assert len({tuple(np.round(r, 4)) for r in flat}) == flat.shape[0]


def test_attention_lstm_outputs_and_grad():
    rng = _RNG(74)
    B, T, S, D, C, M = 2, 4, 5, 3, 5, 4
    ins = {
        "X": rng.randn(B, T, M).astype("float32") * 0.3,
        "EncoderVec": rng.randn(B, S, C).astype("float32"),
        "EncoderProj": rng.randn(B, S, D).astype("float32"),
        "H0": np.zeros((B, D), "float32"),
        "C0": np.zeros((B, D), "float32"),
        "StateProjW": (0.3 * rng.randn(D, D)).astype("float32"),
        "AttnW": (0.3 * rng.randn(2 * D, 1)).astype("float32"),
        "CellW": (0.3 * rng.randn(D + C + M, 4 * D)).astype("float32"),
        "CellB": np.zeros((1, 4 * D), "float32"),
        "EncoderLen": np.asarray([S, S - 2], "int32"),
    }
    hid, attn = _run("attention_lstm", ins, ["Hidden", "AttentionWeight"])
    assert hid.shape == (B, T, D) and np.isfinite(hid).all()
    assert attn.shape == (B, T, S)
    # attention over padded encoder steps is masked out, rows sum to 1
    np.testing.assert_allclose(attn.sum(-1), np.ones((B, T)), rtol=1e-5)
    assert np.abs(attn[1, :, S - 2:]).max() < 1e-6
    _shapes("attention_lstm", ins,
            {"Hidden": (B, T, D)}).check_grad(
        ["X", "CellW", "StateProjW"], "Hidden", max_relative_error=2e-2)


def test_attention_lstm_beam_decode_runs():
    rng = _RNG(75)
    B, S, D, C, V, M, K, T = 2, 5, 3, 5, 11, 4, 3, 6
    ins = {
        "EncoderVec": rng.randn(B, S, C).astype("float32"),
        "EncoderProj": rng.randn(B, S, D).astype("float32"),
        "H0": np.zeros((B, D), "float32"),
        "StateProjW": (0.3 * rng.randn(D, D)).astype("float32"),
        "AttnW": (0.3 * rng.randn(2 * D, 1)).astype("float32"),
        "CellW": (0.3 * rng.randn(D + C + M, 4 * D)).astype("float32"),
        "CellB": np.zeros((1, 4 * D), "float32"),
        "Embedding": rng.randn(V, M).astype("float32"),
        "OutW": (0.3 * rng.randn(D, V)).astype("float32"),
        "OutB": np.zeros((1, V), "float32"),
        "EncoderLen": np.asarray([S, S - 2], "int32"),
    }
    ids, scores = _run(
        "attention_lstm_beam_decode", ins,
        ["SentenceIds", "SentenceScores"],
        {"beam_size": K, "max_len": T, "start_id": 1, "end_id": 2})
    assert ids.shape == (B, K, T)
    assert scores.shape == (B, K)
    assert ((ids >= 0) & (ids < V)).all()
    # beams come back best-first: scores sorted descending per batch row
    assert (np.diff(scores, axis=1) <= 1e-6).all()


def test_transformer_smoothed_loss_matches_explicit_soft_label():
    """The factored label-smoothing head in models/transformer.py must be
    numerically identical to the explicit one_hot -> label_smooth ->
    soft-label CE chain it replaces."""
    rng = _RNG(76)
    N, V, eps = 6, 7, 0.1
    logits_v = rng.randn(N, V).astype("float32")
    label_v = rng.randint(0, V, (N, 1)).astype("int64")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        logits = fluid.layers.data("logits", [V])
        label = fluid.layers.data("label", [1], dtype="int64")
        # explicit soft-label chain
        soft = fluid.layers.label_smooth(
            fluid.layers.one_hot(label, depth=V), epsilon=eps)
        explicit = fluid.layers.softmax_with_cross_entropy(
            logits, soft, soft_label=True)
        # factored form (models/transformer.py head)
        hard = fluid.layers.softmax_with_cross_entropy(logits, label)
        neg_sum_logp = fluid.layers.scale(
            fluid.layers.reduce_sum(
                fluid.layers.log_softmax(logits), dim=-1, keep_dim=True),
            scale=-1.0)
        factored = fluid.layers.elementwise_add(
            fluid.layers.scale(hard, scale=1.0 - eps),
            fluid.layers.scale(neg_sum_logp, scale=eps / V))
    exe = fluid.Executor(fluid.CPUPlace())
    e_v, f_v = exe.run(main, feed={"logits": logits_v, "label": label_v},
                       fetch_list=[explicit, factored])
    np.testing.assert_allclose(np.asarray(f_v), np.asarray(e_v),
                               rtol=1e-5, atol=1e-6)


def test_lod_tensor_to_array_round_trip_trains():
    """Gradients must flow through the array round trip: a parameter
    feeding lod_tensor_to_array -> array_to_lod_tensor -> loss trains
    (the op pair's grads are each other)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3, 4], lod_level=1)
        lens = fluid.layers.data("lens", [1], dtype="int64")
        w = fluid.layers.create_parameter([4], "float32", name="w_rt")
        scaled = fluid.layers.elementwise_mul(x, w, axis=-1)
        table = fluid.layers.lod_rank_table(lengths=lens)
        arr = fluid.layers.lod_tensor_to_array(scaled, table)
        back = fluid.layers.array_to_lod_tensor(arr, table)
        loss = fluid.layers.reduce_mean(back)
        from paddle_tpu import backward as bw
        grads = bw.append_backward(loss)
    (gvar,) = [g for p, g in grads if p.name.startswith("w_rt")]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = _RNG(77).randn(2, 3, 4).astype("float32")
    lv = np.asarray([[3], [2]], "int64")
    (gw,) = exe.run(main, feed={"x": xv, "lens": lv}, fetch_list=[gvar])
    # d(mean(x*w))/dw_j = sum over (b, t) of x[b, t, j] / (B*T*D)
    np.testing.assert_allclose(
        np.asarray(gw), xv.sum(axis=(0, 1)) / xv.size, rtol=1e-5)


def test_attention_lstm_zero_length_row_zero_context():
    """ADVICE r4: a row with EncoderLen==0 must yield ZERO attention
    weights (and thus zero context), not uniform attention over
    padding. The C++ interpreter mirrors this (covered by the
    differential fuzz harness for nonzero lengths; this pins the
    zero-length corner on the XLA engine)."""
    rng = _RNG(75)
    B, T, S, D, C, M = 2, 3, 4, 3, 4, 3
    ins = {
        "X": rng.randn(B, T, M).astype("float32") * 0.3,
        "EncoderVec": rng.randn(B, S, C).astype("float32"),
        "EncoderProj": rng.randn(B, S, D).astype("float32"),
        "H0": np.zeros((B, D), "float32"),
        "C0": np.zeros((B, D), "float32"),
        "StateProjW": (0.3 * rng.randn(D, D)).astype("float32"),
        "AttnW": (0.3 * rng.randn(2 * D, 1)).astype("float32"),
        "CellW": (0.3 * rng.randn(D + C + M, 4 * D)).astype("float32"),
        "CellB": np.zeros((1, 4 * D), "float32"),
        "EncoderLen": np.asarray([S, 0], "int32"),
    }
    hid, attn = _run("attention_lstm", ins, ["Hidden", "AttentionWeight"])
    np.testing.assert_allclose(attn[0].sum(-1), np.ones(T), rtol=1e-5)
    assert np.abs(attn[1]).max() == 0.0, "zero-length row must have zero weights"
    assert np.isfinite(hid).all()


def test_lrn_even_n_reference_window():
    """ADVICE r4: for even n the reference window is start=-(n-1)/2 —
    biased toward HIGHER channels. n=4 at channel c must average
    squares over [c-1, c+2], not [c-2, c+1]."""
    rng = _RNG(40)
    x = rng.randn(1, 6, 2, 2).astype("float32")
    n, k, alpha, beta = 4, 2.0, 0.5, 0.75
    (out,) = _run("lrn", {"X": x}, ["Out"],
                  {"n": n, "k": k, "alpha": alpha, "beta": beta})
    sq = x ** 2
    want = np.empty_like(x)
    C = x.shape[1]
    lo_off = (n - 1) // 2
    for c in range(C):
        lo, hi = max(0, c - lo_off), min(C - 1, c + (n - 1 - lo_off))
        acc = sq[:, lo:hi + 1].sum(axis=1)
        want[:, c] = x[:, c] / (k + alpha * acc) ** beta
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
