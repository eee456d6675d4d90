"""Operator-completeness audit: every op the reference registers vs this
registry, with the by-design mapping for each absence.

Run:  JAX_PLATFORMS=cpu python tools/op_audit.py
Exits non-zero if an absence appears that is neither registered here nor
in the documented by-design table below — i.e. a NEW genuine gap.
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REF_OPS_DIR = "/root/reference/paddle/fluid/operators"

# absences with a documented home:
BY_DESIGN = {
    # regex artifacts of the REGISTER_* macro scrape, not ops
    "act_type": "macro argument, not an op",
    "op_name": "macro argument, not an op",
    "op_type": "macro argument, not an op",
    # executor-managed pseudo-ops
    "feed": "executor feeds directly (C++ interp: host-managed)",
    "fetch": "executor fetches directly",
    "delete_var": "XLA owns buffer lifetime",
    "fake_init": "pserver-side init; no pserver (GSPMD)",
    # gRPC/NCCL distributed machinery -> GSPMD + jax.distributed
    # (docs/DISTRIBUTED_DESIGN.md)
    "send": "GSPMD collectives", "recv": "GSPMD collectives",
    "send_barrier": "GSPMD collectives", "fetch_barrier": "GSPMD",
    "listen_and_serv": "no pserver; DistributeTranspiler plan surface",
    "gen_nccl_id": "XLA collectives, no NCCL", "nccl": "XLA collectives",
    "prefetch": "sparse pserver prefetch; scoped out with rationale",
    "checkpoint_notify": "io.save_checkpoint handles checkpoints",
    "ref_by_trainer_id": "pserver machinery",
    "lookup_sparse_table": "pserver sparse table; SelectedRows covers",
    "merge_ids": "pserver sparse machinery",
    "split_ids": "pserver sparse machinery",
    "split_selected_rows": "pserver sparse machinery",
    "split_byref": "pserver sparse machinery",
    "extract_rows": "pserver sparse machinery",
    # legacy/experimental subsystems the reference itself superseded
    "parallel_do": "ParallelExecutor (GSPMD) replaces",
    "get_places": "mesh construction replaces",
    "go": "CSP experiment; n/a",
    "tensorrt_engine": "CUDA-specific; XLA is the deploy compiler",
    # While-RNN memory machinery -> lax.scan lowering design
    "rnn_memory_helper": "lax.scan carries state",
    "shrink_rnn_memory": "padded-batch design (docs/LOD_DESIGN.md)",
    "max_sequence_len": "padded-batch design",
    "split_lod_tensor": "padded/mask design (docs/LOD_DESIGN.md)",
    "merge_lod_tensor": "padded/mask design",
    # readers -> reader/decorator.py + PyReader + open_files
    "create_custom_reader": "reader combinators",
    "read": "PyReader/open_files design",
    # naming: the reference registers the DYNAMIC rnn ops under the bare
    # names; this registry uses the layer-facing names
    "lstm": "registered as dynamic_lstm",
    "lstmp": "registered as dynamic_lstmp",
    "gru": "registered as dynamic_gru",
    # conditional_block is lowered via the sub-block machinery
    "conditional_block": "ops/control_flow_ops.py cond lowering",
    # ModelAverage keeps its accumulators in optimizer state
    "average_accumulates": "optimizer.ModelAverage internal state",
}


def main():
    pat = re.compile(
        r"REGISTER_OP(?:ERATOR|_WITHOUT_GRADIENT|_CPU_KERNEL"
        r"|_CUDA_KERNEL|_KERNEL)?\s*\(\s*([a-z0-9_]+)")
    ref_ops = set()
    for root, _, files in os.walk(REF_OPS_DIR):
        for fn in files:
            if not fn.endswith((".cc", ".cu", ".h")):
                continue
            try:
                text = open(os.path.join(root, fn), errors="replace").read()
            except OSError:
                continue
            ref_ops.update(pat.findall(text))
    ref_fwd = {o for o in ref_ops if not o.endswith("_grad")}

    import paddle_tpu  # noqa: F401  (registers every op)
    from paddle_tpu.core import op_registry

    ours = set()
    for attr in dir(op_registry):
        v = getattr(op_registry, attr)
        if isinstance(v, dict) and "conv2d" in v:
            ours = set(v)
            break

    unexplained = sorted(
        o for o in ref_fwd if o not in ours and o not in BY_DESIGN)
    covered = len([o for o in ref_fwd if o in ours])
    print("reference fwd ops: %d | registered here: %d | by-design: %d "
          "| UNEXPLAINED: %d"
          % (len(ref_fwd), covered,
             len([o for o in ref_fwd if o in BY_DESIGN and o not in ours]),
             len(unexplained)))
    for o in unexplained:
        print("  UNEXPLAINED:", o)
    return 1 if unexplained else 0


if __name__ == "__main__":
    sys.exit(main())
