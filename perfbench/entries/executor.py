"""Entry ``executor``: the trainer's loop on one chip, ``Executor.run`` a
step (``paddle_tpu/executor.py``)."""

from perfbench import train_common, weights


def run(ctx):
    import paddle_tpu as fluid

    devices = ctx.devices[:1]

    def make_runner(main, startup, loss, named, place):
        scope = fluid.Scope()
        exe = fluid.Executor(place)
        exe.run(startup, scope=scope)
        weights.install(named, scope)

        def run_step(feed):
            return exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                           return_numpy=False)[0]

        return run_step

    return train_common.run_cell(ctx, make_runner, devices)


def make_checker(cell, devices):
    import paddle_tpu as fluid

    return train_common.Checker(cell.config, fluid.TPUPlace())
