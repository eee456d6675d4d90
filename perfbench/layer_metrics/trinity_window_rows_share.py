"""Serving host plane: the rows the window layers' decode reads over what
full caches in their place would have read: the rounds'
``window_rows_visible`` over their ``full_rows_visible``
(``serving/decoder_session.py`` counts both at every decode dispatch).
The median round's pages in use by kind and the pages given back are
printed on an earlier line."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.read_window_rows_share(records)
