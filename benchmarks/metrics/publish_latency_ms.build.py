"""A publish's latency at the server, in ms:
`serve_op_latency_ms{op="build"}` sum / count over the window (a few
builds of one day each, beside eight reading clients: too few for an
end-to-end bound, so it stands here)."""

META = {'layer': 'serve', 'source': 'program_counter', 'unit': 'ms',
        'better': 'lower', 'moves': 'query_completed_per_s'}


def read(r):
    ms, n = r.delta('serve_op_latency_ms_sum', op='build'), \
        r.delta('serve_op_latency_ms_count', op='build')
    return ms / n if ms is not None and n else None
