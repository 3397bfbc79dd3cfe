"""Milliseconds a scan's reply waits between its hand-over to the I/O
loop and the socket's acceptance of its last byte: growth of
`serve_reply_drain_ms_sum` (observed once a reply by the loop, the
window's two scrapes' replies among them) a finished scan."""

META = {'layer': 'serve', 'source': 'program_counter', 'unit': 'ms', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    ms, done = r.delta('serve_reply_drain_ms_sum'), len(r.done('scan'))
    return ms / done if ms is not None and done else None
