"""Milliseconds a publish held the write side of its tree's lock, the
time in which no query of the tree runs:
`serve_tree_lock_held_ms_sum{side="write"}` / `index_publishes_total`
over the window.  The commit's length where the lock goes down to the
commit; the build's where it is held around the build."""

META = {'layer': 'serve', 'source': 'program_counter', 'unit': 'ms',
        'better': 'lower', 'moves': 'query_completed_per_s'}


def read(r):
    held, n = r.delta('serve_tree_lock_held_ms_sum', side='write'), \
        r.delta('index_publishes_total')
    return held / n if held is not None and n else None
