"""A native parser holding the first records of a file, projected for
a scan: what the tests that stage one real batch by hand (the lowered
sparse program, the v5e compiles) feed to DeviceScan._stage_device."""

import itertools


def one_batch_parser(datafile, scan, max_records):
    from dragnet_tpu import native as mod_native
    proj = scan.projection()
    parser = mod_native.NativeParser([p for p, h, d in proj],
                                     [h for p, h, d in proj],
                                     [d for p, h, d in proj])
    with open(datafile, 'rb') as f:
        data = b''.join(itertools.islice(f, max_records))
    parser.parse(data[:data.rfind(b'\n') + 1])
    return parser
