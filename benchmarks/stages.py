"""What the per-layer metrics over the leaf stages share (PR 31).

A leaf stage (`obs_metrics.leaf_stage`, PR 26) observes
`stage_ms{stage}` whenever it ends, and the server observes
`serve_op_latency_ms{op}` at the end of every request.  With S(x) the
growth of `stage_ms_sum{stage=x}` over the window and R the growth of
`serve_op_latency_ms_sum{op=<family>}`, a share is 100 S/R: the two
scrapes lie around a closed loop's window, so every request they count
began and ended between them.  Every function returns None when the
server never wrote the stages (a program older than PR 26) or no
request of the family finished.
"""

import readers

# the request thread's leaves, which do not overlap: the scan's own,
# on a mesh's sparse lane the merge of the chips' sets (PR 35), and the
# reply's two (PR 37: the aggregate's emission in output order, the
# formatting of the lines).  `scan.parse` and `scan.read` are not among
# them: since PR 30 they run on a producer thread beside these, so the
# sum of all leaves may pass the request
SCAN_THREAD = ('scan.parse_wait', 'scan.stage', 'scan.upload',
               'scan.dispatch', 'scan.device_wait', 'scan.fetch',
               'scan.emit', 'scan.sparse_merge', 'scan.order',
               'reply.format')
BUILD_THREAD = SCAN_THREAD + ('index_build.prepare', 'index_build.commit')
QUERY_THREAD = ('index_query_stack.load', 'index_query_stack.sort',
                'index_fold.stage', 'index_fold.dispatch',
                'index_fold.device_wait', 'index_fold.fetch')
HOST_STAGING = ('scan.stage', 'scan.upload', 'scan.dispatch')

# the leaf whose presence says that the server writes the family's stages
_PROBE = {'scan': 'scan.read', 'build': 'scan.read',
          'query': 'index_fold.stage'}


def request_ms(r, op):
    """R: the server's own milliseconds over the family's requests of
    the window; None where none finished or no stage was written."""
    if r.delta('stage_ms_count', stage=_PROBE[op]) is None:
        return None
    return r.delta('serve_op_latency_ms_sum', op=op) or None


def share_pct(r, op, *stages, waited_ms=0.0):
    """100 (S(stages) + what the request waited outside them) / R."""
    total = request_ms(r, op)
    if total is None:
        return None
    return 100.0 * ((readers.stage_ms(r, *stages) or 0.0) + waited_ms) / total


def unattributed_pct(r, op, leaves, waited_ms=0.0):
    """100 - the share of the request thread's leaves (and of what the
    request waited before them): what the spans still miss."""
    covered = share_pct(r, op, *leaves, waited_ms=waited_ms)
    return None if covered is None else 100.0 - covered


def ratio(r, above, below):
    """Growth of one counter over growth of another; a counter that
    was never written has not grown."""
    n = r.delta(below)
    if not n:
        return None
    return (r.delta(above) or 0.0) / n


def per_device_query(r, stage):
    """S(stage) a query that reached the device: the window's finished
    queries less those the result cache answered."""
    ms = readers.stage_ms(r, stage)
    reached = len(r.done('query')) - \
        (r.delta('serve_result_cache_hits_total') or 0.0)
    return ms / reached if ms is not None and reached > 0 else None
