"""Milliseconds of a build's thread before its first batch: the request's
resolution on the server (`serve.resolve`: the config's load, the
datasource, the query) and the scan's set-up (`scan.init`: the file list,
the lane, the scanners and their device objects, the parser, the
producer's start), S(`serve.resolve`) + S(`scan.init`) a finished
request."""

import spans

META = {'layer': 'engine', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    if r.delta('stage_ms_count', stage='scan.init') is None:
        return None
    return spans.per_request_ms(r, 'build', 'serve.resolve', 'scan.init')
