"""Compilations inside the measured window; should read 0."""

import readers

META = {'layer': 'compile cache', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return readers.window_compiles(r)
