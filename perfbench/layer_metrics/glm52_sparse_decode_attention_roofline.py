"""Kernels: absorbed-form decode attention over the selected rows, each
selected row read once for all heads, against the device time of the
COMPOSED path that reads them: the gather that lays the chosen rows of the
paged pool out (with its page arithmetic, found by their results' shapes)
and ``sparse_latent_decode_attention``, which streams the gathered rows.
The kernel alone runs at the memory's rate and the gather at an eighth of
it, so the kernel's own time would point the wrong way: a kernel that
fetched the chosen rows itself raises this share."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.sparse_decode_attention_roofline(records)
