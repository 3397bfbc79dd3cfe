"""Share of a scan's time that its thread spends staging, uploading
and dispatching batches: `stage_ms{scan.stage}` + `{scan.upload}` +
`{scan.dispatch}` over the window / `serve_op_latency_ms{op=scan}`."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return stages.share_pct(r, 'scan', *stages.HOST_STAGING)
