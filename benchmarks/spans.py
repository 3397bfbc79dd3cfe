"""What the per-layer metrics over the request between its device
phases share (PR 42).  The program counts its own coverage: every leaf
stage adds its self time to a total of its thread where it ends, and
the server observes `serve_leaf_ms{op}`, the request's growth of that
total, beside `serve_op_latency_ms{op}`.  Every function returns None
where the server never wrote what it reads (a program older than PR 42)
or no request of the family finished."""

import readers

UNNAMED = 'host: nothing traced'


def coverage_pct(r, op):
    """100 x (what the family's requests spent under leaf stages on
    their own threads, whatever the leaves are called, + what they
    waited for an execution slot) / their latency on the server.

    `serve_queue_wait_ms` carries no `op`: the wait is every
    request's of the window, so the share is the family's only in a
    window that serves one family, as every cell's does (its scrapes
    and control ops take the fast path and wait 0)."""
    covered = r.delta('serve_leaf_ms_sum', op=op)
    total = r.delta('serve_op_latency_ms_sum', op=op)
    if covered is None or not total:
        return None
    waited = r.delta('serve_queue_wait_ms_sum') or 0.0
    return 100.0 * (covered + waited) / total


def named_gap_pct(r):
    """Of the seconds of the ten longest device-idle gaps of the traced
    window (`breakdown.idle_gaps`, the first chip's), the share that a
    host span names: every gap but those the reduction calls `host:
    nothing traced` (`trace/reduce.name_gap`: no event, or none that
    covers half of the gap alone or together with others)."""
    gaps = ((r.trace or {}).get('breakdown') or {}).get('idle_gaps')
    total = sum(s for _name, s in gaps or [])
    if not total:
        return None
    named = sum(s for name, s in gaps if not name.startswith(UNNAMED))
    return 100.0 * named / total


def per_request_ms(r, op, *stages):
    """S(stages) a finished request of the family, in ms."""
    ms, done = readers.stage_ms(r, *stages), len(r.done(op))
    return ms / done if ms is not None and done else None
