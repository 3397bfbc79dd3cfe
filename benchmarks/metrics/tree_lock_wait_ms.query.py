"""Milliseconds a finished query of the readers waited to enter the
read side of its tree's lock: growth of
`serve_tree_lock_wait_ms_sum{side="read"}` over the window / the
readers' finished queries (the histogram observes a wait, not an
entry, so its own count is no divisor; the publisher's two queries a
publish wait there too, a handful among some 1,500).  A program without
the histogram reads nothing."""

META = {'layer': 'serve', 'source': 'program_span', 'unit': 'ms',
        'better': 'lower', 'moves': 'query_completed_per_s'}


def read(r):
    waited, n = r.delta('serve_tree_lock_wait_ms_sum', side='read'), \
        len(r.done('query'))
    return waited / n if waited is not None and n else None
