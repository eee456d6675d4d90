"""Model-zoo benchmark CLI.

Reference parity: benchmark/fluid/fluid_benchmark.py + args.py — one driver
over the models zoo with --model / --batch_size / --update_method /
--device, reporting per-pass throughput. TPU-first differences:
  * --update_method local|spmd|multiproc: `spmd` runs GSPMD data-parallel
    over the visible devices via ParallelExecutor (the gpus>1 path);
    `multiproc` expects torchrun-style env (PADDLE_TRAINER_ID/
    PADDLE_TRAINERS) and uses jax.distributed, the nccl2 analog.
  * --device TPU|CPU (GPU has no meaning here).
  * --use_fake_data feeds one synthetic host batch repeatedly;
    --use_reader_op draws input on-device from the in-graph random reader
    (no host link traffic at all, the bench.py configuration).
  * --amp applies the bf16 AMP program rewrite.

Usage:
    python benchmark/fluid_benchmark.py --model resnet --batch_size 32 \
        --iterations 30 --device CPU
"""

import argparse
import os
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

BENCHMARK_MODELS = [
    "mnist", "resnet", "vgg", "se_resnext", "stacked_lstm",
    "machine_translation", "transformer",
]


def parse_args():
    parser = argparse.ArgumentParser("paddle_tpu model benchmarks.")
    parser.add_argument("--model", type=str, choices=BENCHMARK_MODELS,
                        default="resnet")
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--learning_rate", type=float, default=0.001)
    parser.add_argument("--skip_batch_num", type=int, default=5,
                        help="warmup iterations excluded from timing")
    parser.add_argument("--iterations", type=int, default=80)
    parser.add_argument("--pass_num", type=int, default=1)
    parser.add_argument("--device", type=str, default="TPU",
                        choices=["TPU", "CPU"])
    parser.add_argument("--update_method", type=str, default="local",
                        choices=["local", "spmd", "multiproc"])
    parser.add_argument("--num_devices", type=int, default=0,
                        help="devices for spmd (0 = all visible)")
    parser.add_argument("--infer_only", action="store_true")
    parser.add_argument("--use_fake_data", action="store_true")
    parser.add_argument("--use_reader_op", action="store_true",
                        help="in-graph random reader instead of host feeds")
    parser.add_argument("--amp", action="store_true",
                        help="bf16 AMP program rewrite")
    parser.add_argument("--pallas_rnn", action="store_true",
                        help="route dynamic_lstm/gru through the fused "
                             "Pallas kernels (FLAGS_use_pallas_lstm/gru)")
    parser.add_argument("--memory_optimize", action="store_true")
    parser.add_argument("--gradient_merge", type=int, default=0,
                        metavar="K",
                        help="accumulate K microbatches per optimizer "
                             "step (multi_batch_merge capability)")
    parser.add_argument("--fuse_elewise", action="store_true",
                        help="run the fuse_elewise_add_act pass "
                             "(BuildStrategy.fuse_elewise_add_act_ops)")
    parser.add_argument("--profile", action="store_true",
                        help="profile the timed region (chrome trace)")
    parser.add_argument("--profile_path", type=str,
                        default="/tmp/fluid_benchmark_trace")
    return parser.parse_args()


def _image_inputs(fluid, args, shape, classes):
    """(image var, label var): host-fed data layers, or the in-graph
    random reader when --use_reader_op (no host link traffic)."""
    bs = args.batch_size
    if args.use_reader_op:
        img, label = fluid.layers.random_data_generator(
            shapes=[[bs, *shape], [bs, 1]], dtypes=["float32", "int64"],
            int_high=classes - 1)
        return img, label, {}
    rng = np.random.RandomState(7)
    img = fluid.layers.data("pixel", list(shape))
    label = fluid.layers.data("label", [1], dtype="int64")
    batch = {"pixel": rng.rand(bs, *shape).astype("float32"),
             "label": rng.randint(0, classes, (bs, 1)).astype("int64")}
    return img, label, batch


def _build_model(fluid, args):
    """Returns (loss, feed_fn) — feed_fn() -> feed dict for one batch."""
    bs = args.batch_size
    rng = np.random.RandomState(7)
    name = args.model
    if args.use_reader_op and name not in (
            "mnist", "resnet", "vgg", "se_resnext"):
        raise SystemExit(
            "--use_reader_op is wired for the image models only; "
            "%s feeds from the host" % name)

    if name == "mnist":
        from paddle_tpu import nets

        shape, classes = (1, 28, 28), 10
        img, label, batch = _image_inputs(fluid, args, shape, classes)
        c1 = nets.simple_img_conv_pool(img, filter_size=5, num_filters=20,
                                       pool_size=2, pool_stride=2,
                                       act="relu")
        c2 = nets.simple_img_conv_pool(c1, filter_size=5, num_filters=50,
                                       pool_size=2, pool_stride=2,
                                       act="relu")
        predict = fluid.layers.fc(c2, classes, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(predict, label))
    elif name in ("resnet", "vgg", "se_resnext"):
        shape = (3, 224, 224) if name != "vgg" else (3, 32, 32)
        classes = 1000 if name != "vgg" else 10
        img, label, batch = _image_inputs(fluid, args, shape, classes)
        if name == "resnet":
            from paddle_tpu.models import resnet

            predict = resnet.resnet_imagenet(img, classes)
        elif name == "vgg":
            from paddle_tpu.models.vgg import vgg16_bn_drop

            net = vgg16_bn_drop(img)
            predict = fluid.layers.fc(net, classes, act="softmax")
        else:
            from paddle_tpu.models.se_resnext import se_resnext_imagenet

            predict = se_resnext_imagenet(img, classes)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(predict, label))
    elif name == "stacked_lstm":
        from paddle_tpu.models import stacked_lstm as m

        seq = 80
        loss, feeds, _ = m.build(seq_len=seq)
        batch = {
            "words": rng.randint(0, 5000, (bs, seq)).astype("int64"),
            "length": np.full((bs, 1), seq, "int64"),
            "label": rng.randint(0, 2, (bs, 1)).astype("int64"),
        }
    elif name == "machine_translation":
        from paddle_tpu.models import machine_translation as m

        loss, feeds, _ = m.build()
        seq = 32
        # build() returns (src, src_len, tgt, label, label_mask) vars; key
        # the batch by their actual names, no positional remapping
        src, src_len, tgt, label, label_mask = feeds
        batch = {
            src.name: rng.randint(1, 1000, (bs, seq)).astype("int64"),
            src_len.name: np.full((bs, 1), seq, "int64"),
            tgt.name: rng.randint(1, 1000, (bs, seq)).astype("int64"),
            label.name: rng.randint(1, 1000, (bs, seq)).astype("int64"),
            label_mask.name: np.ones((bs, seq), "float32"),
        }
    elif name == "transformer":
        from paddle_tpu.models import transformer as m

        seq = 64
        loss, feeds, _ = m.build(max_length=seq)
        batch = {
            "src_word": rng.randint(1, 1000, (bs, seq)).astype("int64"),
            "src_len": np.full((bs, 1), seq, "int64"),
            "trg_word": rng.randint(1, 1000, (bs, seq)).astype("int64"),
            "trg_len": np.full((bs, 1), seq, "int64"),
            "label": rng.randint(1, 1000, (bs, seq)).astype("int64"),
        }
        batch = {k: v for k, v in batch.items()
                 if any(f.name == k for f in feeds)}
    else:
        raise ValueError(name)

    return loss, (lambda: batch)


def main():
    args = parse_args()

    import jax

    if args.device == "CPU":
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid

    if args.update_method == "multiproc":
        from paddle_tpu.parallel import init_distributed

        init_distributed()

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 1
    startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        loss, feed_fn = _build_model(fluid, args)
        if not args.infer_only:
            fluid.optimizer.Adam(args.learning_rate).minimize(loss)
    if args.infer_only:
        main_prog = main_prog.clone(for_test=True)
    if args.amp:
        from paddle_tpu.transpiler import rewrite_program_amp

        rewrite_program_amp(main_prog, "bfloat16")
    if args.pallas_rnn:
        from paddle_tpu import flags as _flags

        _flags.set_flag("use_pallas_lstm", True)
        _flags.set_flag("use_pallas_gru", True)
    if args.memory_optimize:
        from paddle_tpu.transpiler import memory_optimize

        memory_optimize(main_prog)
    if args.gradient_merge > 1:
        from paddle_tpu.transpiler import rewrite_program_gradient_merge

        rewrite_program_gradient_merge(
            main_prog, startup, k_steps=args.gradient_merge, avg=True)
    if args.fuse_elewise and args.update_method == "local":
        from paddle_tpu.core.passes import apply_pass

        apply_pass(main_prog, "fuse_elewise_add_act")

    if args.device == "CPU":
        place = fluid.CPUPlace()
    else:
        # --device TPU means the chip: TPUPlace() alone would resolve to
        # a CPU device on a host without one and print a samples/sec
        fluid.require_accelerator(max(1, args.num_devices or 1))
        place = fluid.TPUPlace()

    if args.update_method in ("spmd", "multiproc"):
        build_strategy = fluid.BuildStrategy()
        build_strategy.fuse_elewise_add_act_ops = bool(args.fuse_elewise)
        exe = fluid.Executor(place)
        exe.run(startup)
        pexe = fluid.ParallelExecutor(
            use_tpu=args.device != "CPU",
            loss_name=loss.name,
            main_program=main_prog,
            build_strategy=build_strategy,
            num_devices=args.num_devices or None,
        )
        run = lambda fetch: pexe.run(
            fetch_list=fetch, feed=feed_fn())
    else:
        exe = fluid.Executor(place)
        exe.run(startup)
        run = lambda fetch: exe.run(
            main_prog, feed=feed_fn(), fetch_list=fetch)

    for pass_id in range(args.pass_num):
        for i in range(args.skip_batch_num):
            run([])
        run([loss])  # sync

        if args.profile and pass_id == 0:
            from paddle_tpu import profiler

            prof = profiler.profiler("All", profile_path=args.profile_path)
            prof.__enter__()
        t0 = time.perf_counter()
        for i in range(args.iterations - 1):
            if args.profile and pass_id == 0:
                with profiler.RecordEvent("iter_%d" % i):
                    run([])
            else:
                run([])
        out = run([loss])
        dt = time.perf_counter() - t0
        if args.profile and pass_id == 0:
            prof.__exit__(None, None, None)
            print("chrome trace written to %s" % args.profile_path)

        lv = float(np.ravel(np.asarray(out[0]))[0])
        ips = args.iterations * args.batch_size / dt
        print("pass %d: loss=%.4f, %.2f samples/sec (%.1f ms/iter)"
              % (pass_id, lv, ips, 1000.0 * dt / args.iterations))


if __name__ == "__main__":
    main()
