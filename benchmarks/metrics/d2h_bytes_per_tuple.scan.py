"""Bytes fetched from the device per tuple of the reply:
`device_d2h_bytes` over the window / `--points` lines of the finished
scans' replies.  An exact count."""

META = {'layer': 'engine', 'source': 'program_counter', 'unit': 'bytes/tuple', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    moved = r.delta('device_d2h_bytes')
    tuples = sum(o.out.count(b'\n') for o in r.done('scan'))
    return moved / tuples if moved is not None and tuples else None
