"""Serving host plane: share of the rounds' host time in which the decode
worker's thread was not running (1 - thread CPU time / wall), the host
time being the ``round`` less its ``wait``, ``step.dispatch`` AND
``prefill.dispatch`` children: a prefill dispatch waits for the device as
a decode dispatch does, and is no wait for the interpreter lock."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.read_rounds(records, lib.worker_offcpu_share)
