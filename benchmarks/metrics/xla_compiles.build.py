"""Programs XLA compiled inside the window: growth of
`xla_compiles_total` (`jax.monitoring`: real compilations only, where
`window_compiles.build` also counts loads from the cache); should read 0."""

META = {'layer': 'compile cache', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return r.delta('xla_compiles_total')
