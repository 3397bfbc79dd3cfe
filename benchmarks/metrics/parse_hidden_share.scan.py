"""Share of a scan's batches whose parse was wholly hidden: the batch
was waiting when the request's thread asked for it,
`scan_batches_ready` / `scan_batches_handed` over the window."""

import stages

META = {'layer': 'parse', 'source': 'program_counter', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    hidden = stages.ratio(r, 'scan_batches_ready', 'scan_batches_handed')
    return None if hidden is None else 100.0 * hidden
