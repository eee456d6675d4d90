"""Serving host plane: the latent rows a layer's decode attention reads over
the rows its slots hold: the rounds' ``latent_rows_selected`` over their
``latent_rows_resident`` (``serving/decoder_session.py`` counts both at
every decode dispatch)."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.selected_rows_share(records)
