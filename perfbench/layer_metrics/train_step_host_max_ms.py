"""Model step: the longest step of the window on the host's clock. Beside
the rate, which is taken over all steps, it shows a stall of one step (a
full collection over the program's IR, a late dispatch), whatever the
median step does. The step into which the profiler's own stop fell (some
seconds of writing the trace) is not the program's and is left out."""


def read(records):
    train = records.get("train")
    if not train or not train["step_seconds"]:
        return None
    skip = train.get("profiler_stop_step")
    steps = [s for i, s in enumerate(train["step_seconds"]) if i != skip]
    return 1e3 * max(steps) if steps else None
