"""Offline MFU cost model: per-fused-op flops + HBM bytes from the
TPU-target lowering, no chip required.

Perf planning that does not spend chip time: this tool traces a whole
train step (ResNet-50 at batch 128, Transformer-base at 64 x 256) — the
models' own program builders, the
bf16 AMP rewrite, and the TPU kernel selection (ambient platform "tpu"
picks the Pallas flash-attention path, not the CPU reference path) —
then walks the jaxpr with an XLA-style fusion-group model:

* every matmul/conv/pallas kernel is its own group (the MXU ops XLA
  never merges with each other);
* connected chains of fusible ops (elementwise, broadcast, transpose,
  reduce, ...) merge, and a fusible chain with a single heavy consumer
  or producer folds into it (XLA's loop/input/output fusion on TPU);
* a group's HBM bytes are the values crossing its boundary, counted
  once — the perfect-fusion traffic floor;
* group time = max(flops / peak_flops, bytes / hbm_bw)  (roofline).

Output: a JSONL artifact (one record per fused group, aggregated by
signature) + a summary with predicted step time / MFU at the modeled
chip's published peaks (MODELED_CHIP in the one peak table,
paddle_tpu/observability/telemetry.CHIP_PEAKS). A prediction is not a
measurement: nothing here runs on a chip.

Reference discipline: /root/reference/tools/timeline.py:37-120 commits
the trace-analysis path; this is the same idea made chip-independent.

Usage (CPU host):
  JAX_PLATFORMS=cpu python tools/hlo_cost_model.py --model resnet50 \
      --out /tmp/hlo_cost_model_resnet50.jsonl

Caveats (stated in the artifact): fusion grouping is a model of XLA's
decisions, not a readback of them; pallas_call HBM bytes are an upper
bound (grid steps whose index map revisits a block may be served from
VMEM); while_loop trip counts are unknown statically (reported with
multiplier 1). Totals are cross-checked against the analytic FLOP
accounting (2 FLOPs per MAC, the convention of the chips' peaks).
"""

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the chip this model prices a step for: its published peaks, from the
# one peak table
from paddle_tpu.observability.telemetry import CHIP_PEAKS  # noqa: E402

MODELED_CHIP = "tpu v5 lite"
PEAK_FLOPS = CHIP_PEAKS[MODELED_CHIP].bf16_flops
HBM_BW = CHIP_PEAKS[MODELED_CHIP].hbm_bytes_per_sec

HEAVY = {"dot_general", "conv_general_dilated", "pallas_call",
         "sort", "scatter", "scatter-add", "top_k", "while",
         "reduce_window_max", "reduce_window_sum", "select_and_scatter_add"}

# fusible ops whose cost is one pass over their elements
_ELEMENTWISE_1 = {
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "neg", "sign",
    "abs", "floor", "ceil", "round", "exp", "log", "log1p", "expm1",
    "tanh", "logistic", "rsqrt", "sqrt", "erf", "erf_inv", "erfc",
    "integer_pow", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "ge",
    "gt", "le", "lt", "select_n", "clamp", "nextafter", "sin", "cos",
    "atan2", "square", "is_finite", "convert_element_type", "bitcast_convert_type",
    "copy", "real", "imag", "stop_gradient",
}
_SHAPE_ONLY = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "pad", "rev", "iota", "gather", "split",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
           "cumlogsumexp", "cummax", "reduce_precision"}


def _nbytes(aval):
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:
        return 0


def _size(aval):
    try:
        return int(aval.size)
    except Exception:
        return 0


def _dot_flops(eqn):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    k = 1
    for d in lc:
        k *= lhs.shape[d]
    batch = 1
    for d in lb:
        batch *= lhs.shape[d]
    m = max(1, _size(lhs) // max(1, k * batch))
    n = max(1, _size(rhs) // max(1, k * batch))
    return 2 * batch * m * n * k


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1) or 1
    # kernel spatial size x input channels per group, from the rhs spec
    rhs_shape = rhs.shape
    spatial = 1
    for d in dn.rhs_spec[2:]:
        spatial *= rhs_shape[d]
    cin_per_group = rhs_shape[dn.rhs_spec[1]]
    flops = 2 * _size(out) * cin_per_group * spatial
    # an input-dilated conv (the data-grad of a strided conv) lands a
    # real MAC only on every stride-th tap: the naive count over the
    # zero-dilated input overstates by prod(lhs_dilation)
    for d in (eqn.params.get("lhs_dilation") or ()):
        flops //= max(1, int(d))
    return flops


def eqn_flops(eqn):
    p = eqn.primitive.name
    if p == "dot_general":
        return _dot_flops(eqn)
    if p == "conv_general_dilated":
        return _conv_flops(eqn)
    if p in _ELEMENTWISE_1:
        return sum(_size(v.aval) for v in eqn.outvars)
    if p in _REDUCE:
        return sum(_size(v.aval) for v in eqn.invars)
    if p in _SHAPE_ONLY:
        return 0
    if p in ("reduce_window_max", "reduce_window_sum",
             "select_and_scatter_add"):
        win = eqn.params.get("window_dimensions", ())
        mult = 1
        for w in win:
            mult *= w
        return _size(eqn.outvars[0].aval) * mult
    if p == "sort":
        n = _size(eqn.invars[0].aval)
        return int(n * max(1, math.log2(max(2, n))))
    # default: one pass over the output
    return sum(_size(v.aval) for v in eqn.outvars)


def _subjaxprs(eqn):
    """(jaxpr, multiplier, tag) for eqns that carry inner jaxprs."""
    p = eqn.primitive.name
    params = eqn.params
    if p in ("pjit", "jit", "closed_call", "core_call", "remat",
             "checkpoint", "custom_vjp_call", "custom_jvp_call",
             "custom_vjp_call_jaxpr"):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            j = params.get(key)
            if j is not None:
                yield j, 1, p
                return
    if p == "scan":
        yield params["jaxpr"], int(params.get("length", 1)), "scan"
    elif p == "while":
        # trip count is dynamic: report body once, flagged in the record
        yield params["body_jaxpr"], 1, "while"
    elif p == "cond":
        branches = params.get("branches", ())
        if branches:
            # cost the most expensive branch
            yield max(branches,
                      key=lambda b: sum_flops_recursive(
                          getattr(b, "jaxpr", b))), 1, "cond"


def _is_var(v):
    return type(v).__name__ != "Literal"


def _param_key(params):
    """Hashable digest of eqn params; raises for opaque (jaxpr-carrying)
    params so callers can skip CSE for those eqns."""
    parts = []
    for k in sorted(params):
        v = params[k]
        if hasattr(v, "jaxpr") or type(v).__name__ in ("Jaxpr",
                                                       "ClosedJaxpr"):
            raise TypeError("opaque")
        parts.append((k, repr(v)))
    return tuple(parts)


def optimize_jaxpr(jaxpr, rounds=2):
    """DCE + common-subexpression elimination, approximating what XLA
    does before fusion. Needed because every grad op's lowering is built
    with jax.vjp, which RE-TRACES the forward: the raw jaxpr holds each
    forward conv/matmul twice (once from the forward op, once inside the
    grad op's vjp), and XLA's CSE collapses them — a cost model that
    counts both overstates flops ~2x (measured: 211 convs raw vs ~158
    real for ResNet-50 train). Top-level only: wrapper subjaxprs are
    rare in executor traces (ops lower inline)."""
    from jax.extend import core as jcore

    for _ in range(rounds):
        # ---- DCE (backward liveness) ----
        needed = {v for v in jaxpr.outvars if _is_var(v)}
        kept = []
        for eqn in reversed(jaxpr.eqns):
            if any(v in needed for v in eqn.outvars) \
                    or getattr(eqn, "effects", None):
                kept.append(eqn)
                for v in eqn.invars:
                    if _is_var(v):
                        needed.add(v)
        eqns = list(reversed(kept))
        # ---- CSE (value numbering) ----
        canon = {}
        table = {}
        new_eqns = []
        for eqn in eqns:
            invars = [canon.get(v, v) if _is_var(v) else v
                      for v in eqn.invars]
            if invars != list(eqn.invars):
                eqn = eqn.replace(invars=invars)
            try:
                pk = _param_key(eqn.params)
            except TypeError:
                new_eqns.append(eqn)
                continue
            key = (eqn.primitive.name, pk,
                   tuple(v if _is_var(v) else ("lit", repr(v))
                         for v in invars))
            try:
                prev = table.get(key)
            except TypeError:   # unhashable corner: keep the eqn
                new_eqns.append(eqn)
                continue
            if prev is not None:
                for mine, theirs in zip(eqn.outvars, prev):
                    canon[mine] = theirs
            else:
                table[key] = list(eqn.outvars)
                new_eqns.append(eqn)
        outvars = [canon.get(v, v) if _is_var(v) else v
                   for v in jaxpr.outvars]
        jaxpr = jcore.Jaxpr(
            jaxpr.constvars, jaxpr.invars, outvars, new_eqns,
            getattr(jaxpr, "effects", frozenset()),
            debug_info=getattr(jaxpr, "debug_info", None))
    return jaxpr


class Group(object):
    __slots__ = ("gid", "kind", "label", "flops", "eqns", "values_in",
                 "values_out", "note")

    def __init__(self, gid, kind, label):
        self.gid = gid
        self.kind = kind        # "heavy" | "fusion"
        self.label = label
        self.flops = 0
        self.eqns = 0
        self.values_in = {}     # id(var) -> bytes  (read from outside)
        self.values_out = {}    # id(var) -> bytes  (visible outside)
        self.note = ""

    def bytes_total(self):
        return sum(self.values_in.values()) + sum(self.values_out.values())


def _pallas_cost(eqn):
    """flops from the kernel jaxpr x grid product; bytes as grid x block
    transfers (upper bound: Mosaic may serve revisited blocks from VMEM)."""
    params = eqn.params
    jaxpr = params.get("jaxpr")
    gm = params.get("grid_mapping")
    grid = 1
    try:
        for g in gm.grid:
            grid *= int(g)
    except Exception:
        grid = 1
    flops = 0
    if jaxpr is not None:
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        flops = sum_flops_recursive(inner) * grid
    # boundary traffic: full operands + outputs at least once; blocks
    # revisited across grid steps make this an underestimate, full-array
    # counting makes it an overestimate for pruned (windowed) kernels —
    # call it the full-tensor floor and note it.
    bts = sum(_nbytes(v.aval) for v in eqn.invars) \
        + sum(_nbytes(v.aval) for v in eqn.outvars)
    name = params.get("name") or "pallas_call"
    return name, flops, bts


def sum_flops_recursive(jaxpr):
    total = 0
    for eqn in jaxpr.eqns:
        subs = list(_subjaxprs(eqn))
        if subs:
            for j, mult, _tag in subs:
                inner = getattr(j, "jaxpr", j)
                total += sum_flops_recursive(inner) * mult
        elif eqn.primitive.name == "pallas_call":
            total += _pallas_cost(eqn)[1]
        else:
            total += eqn_flops(eqn)
    return total


def analyze(jaxpr):
    """Fusion-group the top-level jaxpr. Inner jaxprs (pjit bodies) are
    inlined into the walk; pallas/scan/while stay opaque groups."""
    groups = []
    producer = {}       # var -> group
    var_consumers = {}  # var -> count (for fold-into-consumer decisions)

    def walk_count(j):
        for eqn in j.eqns:
            for v in eqn.invars:
                if hasattr(v, "aval") and not _is_literal(v):
                    var_consumers[v] = var_consumers.get(v, 0) + 1
            for sub, _m, _t in _subjaxprs(eqn):
                inner = getattr(sub, "jaxpr", sub)
                walk_count(inner)

    def _is_literal(v):
        return type(v).__name__ == "Literal"

    def new_group(kind, label):
        g = Group(len(groups), kind, label)
        groups.append(g)
        return g

    def feed(g, eqn, mult=1):
        g.eqns += 1
        if eqn.primitive.name == "pallas_call":
            name, fl, bts = _pallas_cost(eqn)
            g.flops += fl * mult
            g.label = "pallas:" + name
            g.note = "bytes=full-tensor floor (grid revisits not modeled)"
            for v in eqn.invars:
                if not _is_literal(v) and producer.get(v) is not g:
                    g.values_in[v] = _nbytes(v.aval)
            for v in eqn.outvars:
                g.values_out[v] = _nbytes(v.aval)
                producer[v] = g
            return
        g.flops += eqn_flops(eqn) * mult
        for v in eqn.invars:
            if _is_literal(v):
                continue
            pg = producer.get(v)
            if pg is not g:
                g.values_in[v] = _nbytes(v.aval)
        for v in eqn.outvars:
            producer[v] = g
            g.values_out[v] = _nbytes(v.aval)

    def walk(j, mult=1, depth=0):
        for eqn in j.eqns:
            p = eqn.primitive.name
            subs = list(_subjaxprs(eqn))
            if subs and p not in ("scan", "while"):
                # transparent wrappers (pjit/custom_vjp/remat): inline
                for sub, m, _t in subs:
                    inner = getattr(sub, "jaxpr", sub)
                    walk(inner, mult * m, depth + 1)
                # map wrapper outputs to the producing inner groups is
                # overkill here: outputs of the wrapper are produced by
                # the last inner groups; approximate by marking them
                # produced by the newest group so downstream reads don't
                # double-count them as external reads
                if groups:
                    for v in eqn.outvars:
                        producer[v] = groups[-1]
                        groups[-1].values_out[v] = _nbytes(v.aval)
                continue
            if p in ("scan", "while"):
                g = new_group("heavy", p)
                for sub, m, _t in subs:
                    inner = getattr(sub, "jaxpr", sub)
                    g.flops += sum_flops_recursive(inner) * m * mult
                g.eqns += 1
                if p == "while":
                    g.note = "dynamic trip count; body costed once"
                for v in eqn.invars:
                    if not _is_literal(v):
                        g.values_in[v] = _nbytes(v.aval)
                for v in eqn.outvars:
                    producer[v] = g
                    g.values_out[v] = _nbytes(v.aval)
                continue
            if p in HEAVY or p == "pallas_call":
                g = new_group("heavy", p)
                feed(g, eqn, mult)
                continue
            # fusible: join the group of its largest non-literal input if
            # that group is fusible OR this is its single elementwise tail
            best, best_bytes = None, -1
            for v in eqn.invars:
                if _is_literal(v):
                    continue
                pg = producer.get(v)
                if pg is None:
                    continue
                b = _nbytes(v.aval)
                if b > best_bytes:
                    best, best_bytes = pg, b
            if best is not None and (
                    best.kind == "fusion"
                    or _single_use_tail(eqn, best, var_consumers)):
                feed(best, eqn, mult)
            else:
                g = new_group("fusion", p)
                feed(g, eqn, mult)

    def _single_use_tail(eqn, pg, consumers):
        # output fusion: fold an elementwise op into the heavy producer
        # when every value it reads from that producer has no OTHER
        # consumer (bias-add/relu after conv; scale after dot)
        for v in eqn.invars:
            if type(v).__name__ == "Literal":
                continue
            if producer.get(v) is pg and consumers.get(v, 0) > 1:
                return False
        return True

    walk_count(jaxpr)
    walk(jaxpr)

    # prune values_in entries that ended up produced in the same group
    for g in groups:
        for v in list(g.values_in):
            if producer.get(v) is g:
                del g.values_in[v]
        # outputs only count as HBM writes if someone outside reads them
        # or they escape the jaxpr; approximate: keep all (upper bound)
    return groups


def floor_model(jaxpr):
    """Perfect-fusion HBM traffic floor.

    Model: XLA fuses every fusible chain into its heavy neighbor, so the
    only HBM traffic is (a) the step's inputs read + outputs written,
    (b) every heavy op's operand reads and result writes, (c) one write
    for a fusible-produced value a heavy op consumes (the chain must
    materialize its result somewhere for a conv/dot to read it — on TPU
    conv/dot operands are materialized, not streamed). Everything an
    elementwise chain does in between is free. Real XLA sits between
    this floor and the per-chain ceiling the group table reports.

    Returns totals plus by-dtype and by-heavy-kind splits — the dtype
    split is the actionable part (f32 bytes that could be bf16).
    """
    seen_writes = set()
    by_dtype = {}
    by_kind = {}
    totals = {"bytes": 0, "flops": 0}

    def _is_literal(v):
        return type(v).__name__ == "Literal"

    def account(nbytes, dtype, kind, is_flops=False):
        totals["bytes"] += nbytes
        by_dtype[dtype] = by_dtype.get(dtype, 0) + nbytes
        k = by_kind.setdefault(kind, {"bytes": 0, "flops": 0})
        k["bytes"] += nbytes

    producer_fusible = {}

    def walk(j, mult=1):
        for eqn in j.eqns:
            p = eqn.primitive.name
            subs = list(_subjaxprs(eqn))
            if subs and p not in ("scan", "while"):
                for sub, m, _t in subs:
                    walk(getattr(sub, "jaxpr", sub), mult * m)
                continue
            heavy = p in HEAVY or p == "pallas_call" or p == "scan"
            if heavy:
                kind = p
                if p == "pallas_call":
                    name, fl, _b = _pallas_cost(eqn)
                    kind = "pallas:" + name
                    flops = fl
                elif p in ("scan", "while"):
                    flops = sum(
                        sum_flops_recursive(getattr(sub, "jaxpr", sub)) * m
                        for sub, m, _t in subs)
                else:
                    flops = eqn_flops(eqn)
                totals["flops"] += flops * mult
                by_kind.setdefault(kind, {"bytes": 0, "flops": 0})
                by_kind[kind]["flops"] += flops * mult
                for v in eqn.invars:
                    if _is_literal(v):
                        continue
                    b = _nbytes(v.aval) * mult
                    account(b, str(v.aval.dtype), kind)
                    if producer_fusible.get(v) and v not in seen_writes:
                        seen_writes.add(v)
                        account(b, str(v.aval.dtype), "chain-materialize")
                for v in eqn.outvars:
                    account(_nbytes(v.aval) * mult, str(v.aval.dtype), kind)
            else:
                for v in eqn.outvars:
                    producer_fusible[v] = True
    walk(jaxpr)
    for v in jaxpr.invars:
        account(_nbytes(v.aval), str(v.aval.dtype), "step-io")
    for v in jaxpr.outvars:
        if not type(v).__name__ == "Literal":
            account(_nbytes(v.aval), str(v.aval.dtype), "step-io")
    return totals, by_dtype, by_kind


def summarize(groups, model_flops, label):
    rows = {}
    for g in groups:
        if g.eqns == 0:
            continue
        key = (g.kind, g.label)
        r = rows.setdefault(key, {
            "kind": g.kind, "op": g.label, "count": 0, "flops": 0,
            "hbm_bytes": 0, "note": g.note})
        r["count"] += 1
        r["flops"] += g.flops
        r["hbm_bytes"] += g.bytes_total()
    out = []
    total_t_nameplate = 0.0
    for r in rows.values():
        t_flops = r["flops"] / PEAK_FLOPS
        t_mem = r["hbm_bytes"] / HBM_BW
        r["roofline_us_nameplate"] = round(max(t_flops, t_mem) * 1e6, 1)
        r["bound"] = "hbm" if t_mem > t_flops else "mxu"
        r["intensity_flops_per_byte"] = round(
            r["flops"] / max(1, r["hbm_bytes"]), 1)
        total_t_nameplate += max(t_flops, t_mem)
        out.append(r)
    out.sort(key=lambda r: -r["roofline_us_nameplate"])
    summary = {
        "record": "summary", "model": label,
        "total_flops": int(sum(r["flops"] for r in out)),
        "model_flops_analytic": int(model_flops) if model_flops else None,
        "total_hbm_bytes": int(sum(r["hbm_bytes"] for r in out)),
        "groups": sum(r["count"] for r in out),
        "step_us_roofline_nameplate": round(total_t_nameplate * 1e6, 1),
        "mfu_roofline_nameplate": round(
            (model_flops or sum(r["flops"] for r in out))
            / max(1e-12, total_t_nameplate) / PEAK_FLOPS, 4),
        "peaks": {"chip": MODELED_CHIP,
                  "nameplate_tflops": PEAK_FLOPS / 1e12,
                  "hbm_gb_s": HBM_BW / 1e9},
    }
    return out, summary


# ---------------------------------------------------------------- models

def build_resnet(fluid, bs, img):
    """ResNet-50's train step on in-graph random data."""
    from paddle_tpu.models import resnet
    from paddle_tpu.transpiler import rewrite_program_amp
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 5
    startup.random_seed = 5
    with fluid.program_guard(main_prog, startup):
        pixel, label = fluid.layers.random_data_generator(
            shapes=[[bs, 3, img, img], [bs, 1]],
            dtypes=["float32", "int64"], int_high=999)
        predict = resnet.resnet_imagenet(pixel, 1000, depth=50)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        loss = fluid.layers.mean(cost)
        fluid.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    rewrite_program_amp(main_prog, "bfloat16")
    # 3 x 7.76 GFLOP an image at 224 (forward + backward, 2 FLOPs per
    # MAC, the hardware convention); conv flops scale ~(img/224)^2
    model_flops = bs * 3 * 7.76e9 * (img / 224.0) ** 2
    return main_prog, startup, {}, model_flops


def build_transformer(fluid, bs, seq):
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler import rewrite_program_amp
    import numpy as np
    n_layer, n_head, d_model, d_inner, vocab = 6, 8, 512, 2048, 32000
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main_prog, startup):
        loss, feeds, _ = transformer.build(
            src_vocab_size=vocab, trg_vocab_size=vocab, max_length=seq,
            n_layer=n_layer, n_head=n_head, d_model=d_model,
            d_inner=d_inner, dropout=0.1)
        fluid.optimizer.Adam(learning_rate=2e-4).minimize(loss)
    rewrite_program_amp(main_prog, "bfloat16")
    rng = np.random.RandomState(11)
    feed = {
        "src_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "src_len": np.full((bs, 1), seq, "int64"),
        "trg_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "trg_len": np.full((bs, 1), seq, "int64"),
        "label": rng.randint(1, vocab, (bs, seq)).astype("int64"),
    }
    feed = {k: v for k, v in feed.items()
            if any(f.name == k for f in feeds)}
    # the exact 6N accounting (enc + dec incl. cross-attention)
    n_params = (
        n_layer * (4 * d_model * d_model + 2 * d_model * d_inner)
        + n_layer * (8 * d_model * d_model + 2 * d_model * d_inner))
    model_flops = 6 * n_params * bs * seq
    return main_prog, startup, feed, model_flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "transformer"])
    ap.add_argument("--bs", type=int, default=None)
    ap.add_argument("--img", type=int, default=224)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu",
                    help="lowering target the trace assumes")
    args = ap.parse_args()

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.lowering import BlockLowerer, build_step_fn

    if args.model == "resnet50":
        bs = args.bs or 128
        program, startup, feed, model_flops = build_resnet(
            fluid, bs, args.img)
    else:
        bs = args.bs or 64
        program, startup, feed, model_flops = build_transformer(
            fluid, bs, args.seq)

    place = fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)
    from paddle_tpu.executor import global_scope
    scope = global_scope()
    scope_names = scope.visible_names()

    lowerer = BlockLowerer(program, 0)
    state_in, state_out = lowerer.analyze(scope_names, set(feed))
    fetch_names = []
    step = build_step_fn(program, list(feed), fetch_names, state_in,
                         state_out, platform=args.platform)

    state_avals = {}
    for n in state_in:
        v = scope.find_var(n).value
        state_avals[n] = jax.ShapeDtypeStruct(v.shape, v.dtype)
    feed_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in feed.items()}
    key_aval = jax.ShapeDtypeStruct((2,), "uint32")

    closed = jax.make_jaxpr(step)(state_avals, feed_avals, key_aval)
    raw_eqns = len(closed.jaxpr.eqns)
    opt = optimize_jaxpr(closed.jaxpr)
    print("jaxpr: %d eqns raw -> %d after dce+cse" %
          (raw_eqns, len(opt.eqns)), file=sys.stderr)
    groups = analyze(opt)
    rows, summary = summarize(groups, model_flops, args.model)

    ftot, fdtype, fkind = floor_model(opt)
    floor_np = 0.0
    kind_rows = {}
    for kind, r in fkind.items():
        t_mem = r["bytes"] / HBM_BW
        floor_np += max(r["flops"] / PEAK_FLOPS, t_mem)
        kind_rows[kind] = {
            "flops": int(r["flops"]), "bytes": int(r["bytes"]),
            "floor_us_nameplate": round(
                max(r["flops"] / PEAK_FLOPS, t_mem) * 1e6, 1),
            "bound": "hbm" if t_mem > r["flops"] / PEAK_FLOPS else "mxu"}
    summary.update({
        "hbm_bytes_floor": int(ftot["bytes"]),
        "step_us_floor_nameplate": round(floor_np * 1e6, 1),
        "mfu_floor_nameplate": round(
            (model_flops or ftot["flops"]) / max(1e-12, floor_np)
            / PEAK_FLOPS, 4),
        "floor_bytes_by_dtype": {k: int(v) for k, v in sorted(
            fdtype.items(), key=lambda kv: -kv[1])},
        "floor_by_kind": kind_rows,
    })

    lines = [json.dumps(summary, sort_keys=True)]
    for r in rows:
        r["record"] = "group"
        lines.append(json.dumps(r, sort_keys=True))
    text = "\n".join(lines) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
