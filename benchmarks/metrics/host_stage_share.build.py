"""Share of a build's time that its thread spends staging, uploading
and dispatching batches: `stage_ms{scan.stage}` + `{scan.upload}` +
`{scan.dispatch}` over the window / `serve_op_latency_ms{op=build}`."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return stages.share_pct(r, 'build', *stages.HOST_STAGING)
