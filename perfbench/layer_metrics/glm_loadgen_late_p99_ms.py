"""Load generator: how late it ran while sending prompts of up to 1024
tokens, the 99th percentile over the window's requests of (instant sent -
instant due), in ms. A starved generator must not be read as a fast
server."""

from perfbench import metric_lib


def read(records):
    return metric_lib.serve_percentile(records, "late_ms", 99)
