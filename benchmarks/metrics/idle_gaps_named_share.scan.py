"""Of the seconds of the ten longest device-idle gaps of the traced
window, the share that a host span names: every gap but those the
reduction calls `host: nothing traced` (`spans.named_gap_pct`)."""

import spans

META = {'layer': 'obs', 'source': 'device_trace', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    return spans.named_gap_pct(r)
