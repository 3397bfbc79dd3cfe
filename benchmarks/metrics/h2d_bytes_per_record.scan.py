"""Bytes staged to the device per corpus record: `device_h2d_bytes`
over the window / records scanned.  An exact count."""

import readers

META = {'layer': 'engine', 'source': 'program_counter', 'unit': 'bytes/record', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return readers.h2d_bytes_per_record(r, 'scan')
