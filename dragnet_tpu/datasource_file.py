"""File-backend execution engine: scan, build, query, index-scan,
index-read.

Re-implements lib/datasource-file.js on the host side: input enumeration
(strftime-pruned when the datasource has a time format), concatenated line
parsing, the per-metric scan fan-out for index builds (one pass over raw
data feeds every metric's aggregator), the hour/day index multiplexer keyed
on __dn_ts, and the per-index-file query fan-in.

The aggregation hot path is delegated to engine.py (vectorized/JAX) when
the query shape allows, with scan.py as the exact-semantics fallback.
"""

import os
import queue as mod_queue
import sys
import threading

import numpy as np

from .errors import DNError
from . import jsvalues as jsv
from . import log as mod_log
from . import query as mod_query
from . import ingest as mod_ingest
from . import find as mod_find
from .aggr import Aggregator, PointBlock
from .scan import StreamScan
from .vpipe import Pipeline
from .obs import metrics as obs_metrics

LOG = mod_log.get('datasource-file')


def create_datasource(dsconfig):
    assert dsconfig['ds_backend'] == 'file'
    if not isinstance(dsconfig['ds_backend_config'].get('path'), str):
        return DNError('expected datasource "path" to be a string')
    return DatasourceFile(dsconfig)


def _emit_points(aggr):
    """The aggregate's emission (ordering, decoding) as what a
    ScanResult carries: the columnar result's PointBlock, else the
    list of points()."""
    with obs_metrics.leaf_stage('scan.order'):
        block = aggr.point_block()
        return block if block is not None else aggr.points()


class ScanResult(object):
    """`points` is given as the list of (fields, value) pairs or as a
    columnar result's aggr.PointBlock.  Reading `.points` is always
    the list: a block builds it on first use, so ask `has_points` /
    `npoints` / `block` where the dicts are not wanted."""

    def __init__(self, pipeline, points=None, dry_run_files=None,
                 query=None):
        self.pipeline = pipeline
        self._points = points
        self.dry_run_files = dry_run_files
        self.dry_run_plan = None    # cluster backend: execution plan
        self.parse_plan = None      # scan dry run: DN_PARSE lane info
        self.query = query

    @property
    def block(self):
        p = self._points
        return p if isinstance(p, PointBlock) else None

    @property
    def points(self):
        p = self._points
        return p.points() if isinstance(p, PointBlock) else p

    @points.setter
    def points(self, points):
        self._points = points

    @property
    def has_points(self):
        return self._points is not None

    @property
    def npoints(self):
        return len(self._points or ())

    def clone_for_output(self):
        """An output-formatting view of this result with a PRIVATE
        pipeline (stage names/counters copied, points shared
        read-only).  The CLI output layer mutates the pipeline it
        formats — it appends a Flattener stage and bumps counters — so
        `dn serve` requests coalesced onto one shared execution must
        each format through their own clone, or the second --counters
        dump would show the first request's stages doubled."""
        pl = Pipeline()
        pl.warn_func = None
        for s in self.pipeline.stages:
            stage = pl.stage(s.name)
            stage.counters = dict(s.counters)
            stage.hidden = set(s.hidden)
        rv = ScanResult(pl, points=self._points,
                        dry_run_files=self.dry_run_files,
                        query=self.query)
        rv.dry_run_plan = self.dry_run_plan
        rv.parse_plan = self.parse_plan
        return rv


class DatasourceFile(object):
    def __init__(self, dsconfig):
        bc = dsconfig['ds_backend_config']
        self.ds_format = dsconfig.get('ds_format')
        self.ds_timeformat = bc.get('timeFormat')
        self.ds_timefield = bc.get('timeField')
        self.ds_datapath = bc['path']
        self.ds_indexpath = bc.get('indexPath')
        self.ds_filter = dsconfig.get('ds_filter')

    def close(self):
        pass

    def _vector_scan_cls(self):
        from .device_scan import scan_class
        return scan_class()

    # -- input enumeration ------------------------------------------------

    def _find(self, root, timeformat, start_ms, end_ms, pipeline,
              skip=None):
        """Returns list of (path, stat) or DNError."""
        if end_ms is None:
            return mod_find.find_walk([root], pipeline, skip=skip)
        assert start_ms is not None
        pathenum = mod_find.create_path_enumerator(
            os.path.join(root, timeformat), start_ms, end_ms)
        if isinstance(pathenum, DNError):
            return pathenum
        roots = pathenum.paths()
        return mod_find.find_walk(roots, pipeline, pathenum=pathenum)

    def _scan_init(self, time_after, time_before, pipeline):
        """Common setup for scan and build: format check, file list.
        Returns (files, fmt) or DNError.  (Record-level filtering happens
        downstream in StreamScan / FilterStage.)"""
        if self.ds_timefield is None and \
                (time_before is not None or time_after is not None):
            return DNError('datasource is missing "timefield" for '
                           '"before" and "after" constraints')

        fmt = mod_ingest.parser_for(self.ds_format)
        if isinstance(fmt, DNError):
            return fmt

        if self.ds_timeformat is not None:
            files = self._find(self.ds_datapath, self.ds_timeformat,
                               time_after, time_before, pipeline)
        else:
            if time_before is not None or time_after is not None:
                sys.stderr.write('warn: datasource is missing '
                                 '"timeformat" for "before" and "after" '
                                 'constraints\n')
            files = self._find(self.ds_datapath, None, None, None, pipeline)
        if isinstance(files, DNError):
            return files
        return (files, fmt)

    # -- scan -------------------------------------------------------------

    def scan(self, query, dry_run=False, warn_func=None):
        """Scan raw data to execute a query.  Returns a ScanResult whose
        points are the aggregated output.  (reference:
        lib/datasource-file.js:72-108)"""
        # scan.init: the request's thread from here to the stream's
        # first batch (the file list, the lane, the scanner and its
        # device objects, the parser, the producer's start); ended
        # where the records begin (end_open: _stream_native, or the
        # record loops below)
        with obs_metrics.leaf_stage('scan.init'):
            pipeline = Pipeline()
            pipeline.warn_func = warn_func
            ctx = self._scan_init(query.qc_after, query.qc_before, pipeline)
            if isinstance(ctx, DNError):
                raise ctx
            files, fmt = ctx

            from . import byteparse as mod_byteparse

            if dry_run:
                result = ScanResult(pipeline,
                                    dry_run_files=[p for p, st in files])
                from . import native as mod_native
                lane = mod_byteparse.choose_lane(
                    [query], self.ds_timefield, self.ds_filter, fmt,
                    mod_native.get_lib() is not None)
                result.parse_plan = {'parse_lane': lane.lane,
                                     'parse_mode':
                                         mod_byteparse.parse_mode(),
                                     'reason': lane.reason}
                return result

            LOG.debug('scan start', datapath=self.ds_datapath,
                      nfiles=len(files),
                      nbytes=sum(getattr(st, 'st_size', 0) or 0
                                 for p, st in files))

            # The vectorized engine produces identical results; --warnings
            # needs the per-record host path for ordered warning output.
            # Within the vectorized path, ingest runs one of the DN_PARSE
            # lanes: the native C++ parser (host), the vectorized byte
            # parser (vector/device — byteparse.py), or the Python record
            # path when neither engages.
            from .engine import engine_mode
            use_vector = warn_func is None and engine_mode() != 'host'
            native_lib = None
            lane = None
            if use_vector:
                from . import native as mod_native
                native_lib = mod_native.get_lib()
                lane = mod_byteparse.choose_lane(
                    [query], self.ds_timefield, self.ds_filter, fmt,
                    native_lib is not None)

            if use_vector and (native_lib is not None or lane.engaged):
                scanner = self._scan_native(query, files, fmt, pipeline,
                                            lane)
            elif use_vector:
                from .engine import BATCH_SIZE
                stages = mod_ingest.make_parser_stages(pipeline, fmt)
                # no native library AND the byte lane could not engage:
                # the ineligibility counter must still appear
                mod_byteparse.note_ineligible(stages[0], lane)
                scanner = self._vector_scan_cls()(
                    query, self.ds_timefield, pipeline,
                    ds_filter=self.ds_filter)
                records = mod_ingest.iter_records(
                    mod_ingest.iter_lines([p for p, st in files]), fmt,
                    stages=stages)
                obs_metrics.leaf_stage.end_open('scan.init')
                buf_r, buf_w = [], []
                for fields, value in records:
                    buf_r.append(fields)
                    buf_w.append(value)
                    if len(buf_r) >= BATCH_SIZE:
                        scanner.write_batch(buf_r, buf_w)
                        buf_r, buf_w = [], []
                scanner.write_batch(buf_r, buf_w)
            else:
                from .engine import weights_array
                stages = mod_ingest.make_parser_stages(pipeline, fmt)
                scanner = StreamScan(query, self.ds_timefield, pipeline,
                                     ds_filter=self.ds_filter)
                records = mod_ingest.iter_records(
                    mod_ingest.iter_lines([p for p, st in files]), fmt,
                    stages=stages)
                obs_metrics.leaf_stage.end_open('scan.init')
                for fields, value in records:
                    # weight coercion identical to the vectorized paths
                    # (json-skinner values may be strings/garbage)
                    if not isinstance(value, int):
                        value = float(weights_array([value])[0])
                        value = int(value) if value.is_integer() else value
                    scanner.write(fields, value)

        # scan.finish: the deferred merge (the leaves it opens inside,
        # scan.fetch, scan.emit, scan.sparse_merge, suspend it)
        with obs_metrics.leaf_stage('scan.finish'):
            if hasattr(scanner, 'finish'):
                scanner.finish()   # merge any device-buffered batches
        result = ScanResult(pipeline, points=_emit_points(scanner.aggr),
                            query=query)
        LOG.debug('scan done', npoints=result.npoints,
                  engine=type(scanner).__name__)
        return result

    def _make_parser(self, lane, paths, hints, dicts, parser_stage):
        """Instantiate the selected ingest parser: the byte lane
        (byteparse.ByteParser, numpy or jax structural kernel) when it
        engaged, the native C++ parser otherwise.  A requested-but-
        ineligible byte lane is recorded as a hidden counter."""
        from . import byteparse as mod_byteparse
        if lane is not None:
            mod_byteparse.note_ineligible(parser_stage, lane)
            if lane.engaged:
                return mod_byteparse.ByteParser(
                    paths, hints, dicts,
                    device=(lane.lane == 'device'))
        from . import native as mod_native
        return mod_native.NativeParser(paths, hints, dicts)

    def _scan_native(self, query, files, fmt, pipeline, lane=None):
        """Scan via a columnar parser — the C++ one (host lane) or the
        vectorized byte parser (DN_PARSE=vector|device): one pass over
        the concatenated bytes, projected fields only, batched into
        the vectorized engine.  (The byte stream is the concatenation
        of all files — a partial trailing line joins across file
        boundaries, matching catstreams semantics.)  With
        DN_SCAN_THREADS > 0 the engine step runs on worker threads
        pipelined behind the parse (scan_mt), with byte-identical
        results."""
        from .engine import BATCH_SIZE, NativeColumns, VectorScan
        from . import scan_mt

        stages = mod_ingest.make_parser_stages(pipeline, fmt)
        parser_stage, adapter_stage = stages
        stage_offset = len(pipeline.stages)
        scan_cls = self._vector_scan_cls()
        scanner = scan_cls(
            query, self.ds_timefield, pipeline, ds_filter=self.ds_filter)

        skinner = fmt == 'json-skinner'
        proj = scanner.projection()
        if skinner:
            paths = ['fields.' + p for p, h, d in proj] + ['value']
            hints = [h for p, h, d in proj] + [False]
            dicts = [d for p, h, d in proj] + [True]
        else:
            paths = [p for p, h, d in proj]
            hints = [h for p, h, d in proj]
            dicts = [d for p, h, d in proj]
        parser = self._make_parser(lane, paths, hints, dicts,
                                   parser_stage)
        remap = {p: np_ for p, np_ in
                 zip([p for p, h, d in proj], paths)} if skinner \
            else None

        nworkers = scan_mt.scan_threads()
        use_mt = nworkers > 0 and scan_cls is VectorScan
        # auto-device mode runs the MT host engine too: workers are
        # plain VectorScans, and the device path (the main scanner) can
        # TAKE OVER the stream mid-flight once its background backend
        # probe succeeds and enough work remains — or hand back if it
        # loses its probation window.  (Round 3 pinned auto to the
        # single-threaded path, so auto regressed vs DN_ENGINE=host on
        # multicore hosts before the device ever helped.)
        auto_mt = nworkers > 0 and \
            getattr(scan_cls, 'AUTO_STREAM', False)
        progress_fn = getattr(scanner, 'set_progress', None)

        if use_mt or auto_mt:
            def build_worker(wp):
                wscan = VectorScan(query, self.ds_timefield, wp,
                                   ds_filter=self.ds_filter)
                # workers drain per batch through the recorder; the
                # deferred columnar merge would hold rows past drain
                wscan._defer_enabled = False
                rec = scan_mt.BatchRecorder(wscan.aggr.stage)
                wscan.aggr = rec

                def process(snap):
                    provider = NativeColumns(_remapped(snap, remap))
                    wscan._process(provider,
                                   _batch_weights(skinner, snap,
                                                  snap.batch_size()))
                    return rec.drain()
                return process

            def new_executor():
                # one radix merge per executor epoch: finalize() runs
                # inside finish(), so a device takeover (or the final
                # drain) always observes this executor's batches fully
                # merged, in order
                radix = scan_mt.RadixMerge(scanner)
                return scan_mt.MTScanExecutor(nworkers, build_worker,
                                              radix.apply_calls,
                                              pipeline, stage_offset,
                                              finish_fn=radix.finalize)

            def device_batch(batch, n):
                nlines, nbad = batch.counters()
                _bump_parse_counters(parser_stage, adapter_stage,
                                     nlines, nbad, n)
                weights = _batch_weights(skinner, batch, n)
                scanner.write_native_batch(_remapped(batch, remap),
                                           weights)
                if scanner._disabled:
                    scanner._flush()
                    return False     # hand back to the MT executor
                return True

            def submit_batch(ex, batch, n):
                snap = scan_mt.ParserSnapshot(batch, paths, hints,
                                              dicts)
                _bump_parse_counters(parser_stage, adapter_stage,
                                     snap.nlines, snap.nbad, n)
                if auto_mt:
                    scanner.note_external_batch(n)
                    scanner.shadow_feed(snap, n)
                ex.submit(snap)

            if auto_mt:
                from .device_scan import DeviceScan
                from .vpipe import Pipeline as _Pipeline
                scanner.enable_shadow(
                    lambda: [DeviceScan(query, self.ds_timefield,
                                        _Pipeline(),
                                        ds_filter=self.ds_filter)],
                    lambda snap: NativeColumns(_remapped(snap, remap)),
                    lambda snap, n: _batch_weights(skinner, snap, n))

            self._takeover_stream(
                files, parser, BATCH_SIZE, progress_fn, new_executor,
                submit_batch,
                scanner.take_over_now if auto_mt else None,
                device_batch)
        else:
            def flush(batch):
                n = batch.batch_size()
                if n == 0:
                    return
                nlines, nbad = batch.counters()
                _bump_parse_counters(parser_stage, adapter_stage,
                                     nlines, nbad, n)
                weights = _batch_weights(skinner, batch, n)
                scanner.write_native_batch(_remapped(batch, remap),
                                           weights)

            self._stream_native(files, parser, flush, BATCH_SIZE,
                                progress=progress_fn)
        # counters even when the final batch was empty
        nlines, nbad = parser.counters()
        if nlines:
            parser_stage.counters['ninputs'] = nlines
            parser_stage.counters['noutputs'] = nlines - nbad
            if nbad:
                parser_stage.counters['invalid json'] = nbad
        from . import byteparse as mod_byteparse
        mod_byteparse.publish_counters(parser_stage, parser)
        return scanner

    # -- build / index-scan -----------------------------------------------

    def check_time_args(self, time_after, time_before):
        if time_after is not None and time_before is None:
            return DNError('cannot specify --after without --before')
        if time_before is not None and time_after is None:
            return DNError('cannot specify --before without --after')
        return None

    def check_index_args(self, interval, needsindex, needstime):
        if needsindex and self.ds_indexpath is None:
            return DNError('datasource is missing "indexpath"')
        if needstime and interval != 'all' and self.ds_timefield is None:
            return DNError('datasource is missing "timefield"')
        return None

    def build(self, metrics, interval, time_after=None, time_before=None,
              dry_run=False, warn_func=None):
        from . import resources as mod_resources
        # a full disk / exhausted fd table mid-build (real, or armed
        # enospc/emfile at the sink/journal seams) surfaces as the
        # clean retryable disk_full DNError, never a traceback — the
        # two-phase journal already guarantees the tree is left
        # pre-build or post-build, never torn
        with mod_resources.translate_pressure_errors('index build'), \
                obs_metrics.leaf_stage('scan.init'):
            return self._index_scan_impl(
                metrics, interval, self.ds_filter, time_after,
                time_before, dry_run, sink='index',
                warn_func=warn_func)

    def index_scan(self, metrics, interval, filter=None, time_after=None,
                   time_before=None, warn_func=None):
        with obs_metrics.leaf_stage('scan.init'):
            return self._index_scan_impl(
                metrics, interval, filter, time_after, time_before,
                False, sink='points', warn_func=warn_func)

    def _index_scan_impl(self, metrics, interval, filter, time_after,
                         time_before, dry_run, sink, warn_func=None):
        """One pass over raw data feeding every metric's scan; output goes
        to index files (build) or tagged points (index-scan).  The
        caller's open `scan.init` leaf (see scan()) ends where the
        records begin.
        (reference: lib/datasource-file.js:322-433)"""
        pipeline = Pipeline()
        pipeline.warn_func = warn_func
        error = self.check_time_args(time_after, time_before)
        if error is None:
            error = self.check_index_args(interval, sink == 'index', True)
        if error is not None:
            raise error

        ctx = self._scan_init(time_after, time_before, pipeline)
        if isinstance(ctx, DNError):
            raise ctx
        files, fmt = ctx

        if dry_run:
            return ScanResult(pipeline,
                              dry_run_files=[p for p, st in files])

        LOG.debug('%s start' % ('build' if sink == 'index'
                                else 'index-scan'),
                  datapath=self.ds_datapath, nfiles=len(files),
                  nmetrics=len(metrics), interval=interval)

        queries = [mod_query.metric_query(m, time_after, time_before,
                                          interval, self.ds_timefield)
                   for m in metrics]

        # --warnings needs the per-record host path for ordered
        # warning output (same rule as scan())
        from .engine import engine_mode
        use_vector = warn_func is None and engine_mode() != 'host'
        native_lib = None
        lane = None
        if use_vector:
            from . import native as mod_native
            from . import byteparse as mod_byteparse
            native_lib = mod_native.get_lib()
            lane = mod_byteparse.choose_lane(
                queries, self.ds_timefield, filter, fmt,
                native_lib is not None)

        if native_lib is not None or (lane is not None and
                                      lane.engaged):
            scanners = self._index_scan_native(
                queries, files, fmt, filter, pipeline, lane)
        else:
            stages = mod_ingest.make_parser_stages(pipeline, fmt)
            if lane is not None:
                # no native library AND the byte lane could not
                # engage: the ineligibility counter must still appear
                from . import byteparse as mod_byteparse
                mod_byteparse.note_ineligible(stages[0], lane)

            # The datasource filter is applied once on the shared parse
            # stream; each metric's own filter lives in its StreamScan
            # (reference: lib/datasource-file.js:124-192 vs :403-427).
            ds_filter_stage = None
            if filter is not None:
                from . import krill as mod_krill
                from .scan import FilterStage
                ds_filter_stage = FilterStage(
                    mod_krill.create(filter),
                    pipeline.stage('Datasource filter'))

            scanners = []
            for qi, q in enumerate(queries):
                s = StreamScan(q, self.ds_timefield, pipeline,
                               ds_filter=None)
                pipeline.stage('Add __dn_metric')
                scanners.append(s)

            lines = mod_ingest.iter_lines([p for p, st in files])
            obs_metrics.leaf_stage.end_open('scan.init')
            for fields, value in mod_ingest.iter_records(lines, fmt,
                                                         stages=stages):
                if ds_filter_stage is not None and \
                        not ds_filter_stage.accept(fields):
                    continue
                for s in scanners:
                    s.write(fields, value)

        if sink == 'index':
            # columnar hand-off: each metric's aggregate goes to the
            # index writer as parallel key columns + weights
            # (Aggregator.point_rows) — no per-point field dicts, no
            # __dn_metric tagging pass (index_build_mt routes blocks
            # by position)
            from . import index_build_mt as mod_ibmt
            blocks = []
            # scan.finish: every scanner's deferred merge (its own
            # leaves suspend this one) and its rows
            with obs_metrics.leaf_stage('scan.finish'):
                for s in scanners:
                    if hasattr(s, 'finish'):
                        s.finish()   # merge any device-buffered batches
                    cols, weights = s.aggr.point_rows()
                    blocks.append((list(s.aggr.decomps), cols, weights))
            mod_ibmt.write_index_blocks(metrics, interval,
                                        self.ds_indexpath, blocks)
            return ScanResult(pipeline, points=None)

        tagged = []
        with obs_metrics.leaf_stage('scan.finish'):
            for qi, s in enumerate(scanners):
                if hasattr(s, 'finish'):
                    s.finish()   # merge any device-buffered batches
                for fields, value in s.aggr.points():
                    fields['__dn_metric'] = qi
                    tagged.append((fields, value))
        return ScanResult(pipeline, points=tagged)

    def _index_scan_native(self, queries, files, fmt, filter, pipeline,
                           lane=None):
        """Build fan-out over a columnar parser (native C++ or the
        DN_PARSE byte lane): ONE pass over raw bytes feeds every
        metric's vectorized scan (the reference pipes one parse stream
        into N StreamScans, lib/datasource-file.js:403-427; here one
        columnar provider feeds N engine passes, parallelized across
        worker threads when DN_SCAN_THREADS > 0)."""
        from .engine import (BATCH_SIZE, NativeColumns, VectorPredicate,
                             VectorScan)
        from . import scan_mt
        from .ops.kernels import TRUE

        stages = mod_ingest.make_parser_stages(pipeline, fmt)
        parser_stage, adapter_stage = stages
        stage_offset = len(pipeline.stages)
        scan_cls = self._vector_scan_cls()

        class _Holder(object):
            def __init__(self):
                self.raw_columns = {}
                self.filter_fields = []

        def make_scan_set(pl, cls):
            """The per-pipeline scan state: datasource predicate (+its
            stage) and one scan per metric; identical stage layout on
            the main and every worker pipeline."""
            pred = stage = None
            if filter is not None:
                holder = _Holder()
                pred = VectorPredicate(filter, holder)
                stage = pl.stage('Datasource filter')
            scans = []
            for q in queries:
                s = cls(q, self.ds_timefield, pl, ds_filter=None)
                pl.stage('Add __dn_metric')
                scans.append(s)
            return pred, stage, scans, holder if filter is not None \
                else None

        def make_scan_set_host(pl):
            return make_scan_set(pl, VectorScan)

        ds_pred, ds_stage, scanners, holder = make_scan_set(pipeline,
                                                            scan_cls)

        skinner = fmt == 'json-skinner'
        proj = {}
        if filter is not None:
            for f in holder.filter_fields:
                proj.setdefault(f, [False, True])
        for s in scanners:
            for p, h, d in s.projection():
                ent = proj.setdefault(p, [False, False])
                ent[0] = ent[0] or h
                ent[1] = ent[1] or d

        items = list(proj.items())
        if skinner:
            paths = ['fields.' + p for p, hd in items] + ['value']
            hints = [hd[0] for p, hd in items] + [False]
            dicts = [hd[1] for p, hd in items] + [True]
        else:
            paths = [p for p, hd in items]
            hints = [hd[0] for p, hd in items]
            dicts = [hd[1] for p, hd in items]
        parser = self._make_parser(lane, paths, hints, dicts,
                                   parser_stage)
        remap = {p: np_ for (p, hd), np_ in zip(items, paths)} \
            if skinner else None

        def eval_ds_filter(pred, stage, provider, n):
            stage.bump('ninputs', n)
            out = pred.outcomes(provider)
            nfail = int((out == 2).sum())
            ndrop = int((out == 0).sum())
            if nfail:
                stage.bump('nfailedeval', nfail)
            if ndrop:
                stage.bump('nfilteredout', ndrop)
            alive0 = out == TRUE
            stage.bump('noutputs', int(alive0.sum()))
            return alive0

        # device scans, one metric or many, fold in ONE dispatch per
        # batch with shared columns uploaded once (SURVEY §7.7), on the
        # cluster backend's mesh as off it; the host engine's scans
        # take the batch one after the other
        if scan_cls is VectorScan:
            def process_all(provider, weights, alive0):
                for s in scanners:
                    s._process(provider, weights, alive=alive0)
        else:
            from .device_scan import DeviceScanStack
            process_all = DeviceScanStack(scanners).process

        def process_batch(batch, n):
            """One batch through every metric's scan on this thread
            (the forced-device build and the takeover's device side)."""
            nlines, nbad = batch.counters()
            _bump_parse_counters(parser_stage, adapter_stage,
                                 nlines, nbad, n)
            provider = NativeColumns(_remapped(batch, remap))
            weights = _batch_weights(skinner, batch, n)
            alive0 = None
            if ds_pred is not None:
                alive0 = eval_ds_filter(ds_pred, ds_stage, provider, n)
            process_all(provider, weights, alive0)

        nworkers = scan_mt.scan_threads()
        use_mt = nworkers > 0 and scan_cls is VectorScan
        # auto-device builds mirror the scan path: MT host workers by
        # default, with a coordinated device takeover (and hand-back on
        # lost probation) across all metric scanners
        auto_mt = nworkers > 0 and \
            getattr(scan_cls, 'AUTO_STREAM', False)

        def set_all_progress(done, total):
            for s in scanners:
                if hasattr(s, 'set_progress'):
                    s.set_progress(done, total)
        progress_fn = set_all_progress \
            if any(hasattr(s, 'set_progress') for s in scanners) else None

        if use_mt or auto_mt:
            def build_worker(wp):
                wpred, wstage, wscans, _ = make_scan_set_host(wp)
                recs = []
                for s in wscans:
                    s._defer_enabled = False   # drained per batch
                    rec = scan_mt.BatchRecorder(s.aggr.stage)
                    s.aggr = rec
                    recs.append(rec)

                def process(snap):
                    n = snap.batch_size()
                    provider = NativeColumns(_remapped(snap, remap))
                    weights = _batch_weights(skinner, snap, n)
                    alive0 = None
                    if wpred is not None:
                        alive0 = eval_ds_filter(wpred, wstage,
                                                provider, n)
                    out = []
                    for s, rec in zip(wscans, recs):
                        s._process(provider, weights, alive=alive0)
                        out.append(rec.drain())
                    return out
                return process

            def new_executor():
                # one radix merge per metric scanner per executor epoch
                radixes = [scan_mt.RadixMerge(s) for s in scanners]

                def apply_result(results):
                    for radix, calls in zip(radixes, results):
                        radix.apply_calls(calls)

                def finish_fn():
                    for radix in radixes:
                        radix.finalize()
                return scan_mt.MTScanExecutor(nworkers, build_worker,
                                              apply_result, pipeline,
                                              stage_offset,
                                              finish_fn=finish_fn)

            def take_over():
                if not scanners[0].take_over_now():
                    return False
                # share the probe result so sibling scanners don't
                # each wait on their own probe thread
                for s in scanners[1:]:
                    s._backend_ok = scanners[0]._backend_ok
                return True

            def device_batch(batch, n):
                process_batch(batch, n)
                if any(s._disabled for s in scanners):
                    # coordinated hand-back: all metric scanners leave
                    # the device together
                    for s in scanners:
                        s._flush()
                        s._disabled = True
                    return False
                return True

            def submit_batch(ex, batch, n):
                snap = scan_mt.ParserSnapshot(batch, paths, hints,
                                              dicts)
                _bump_parse_counters(parser_stage, adapter_stage,
                                     snap.nlines, snap.nbad, n)
                if auto_mt:
                    for s in scanners:
                        s.note_external_batch(n)
                    scanners[0].shadow_feed(snap, n)
                ex.submit(snap)

            if auto_mt:
                from .device_scan import DeviceScan
                from .vpipe import Pipeline as _Pipeline
                # the audition replays every metric's scan, so the
                # measured rate reflects the whole build fan-out
                scanners[0].enable_shadow(
                    lambda: [DeviceScan(q, self.ds_timefield,
                                        _Pipeline(), ds_filter=None)
                             for q in queries],
                    lambda snap: NativeColumns(_remapped(snap, remap)),
                    lambda snap, n: _batch_weights(skinner, snap, n),
                    # production passes the shared ds-filter mask as a
                    # non-None alive; the replay must match that shape
                    # or the staged profile misses the program cache
                    make_alive=(
                        (lambda n: np.ones(n, dtype=bool))
                        if filter is not None else None))

            self._takeover_stream(
                files, parser, BATCH_SIZE, progress_fn, new_executor,
                submit_batch,
                take_over if auto_mt else None,
                device_batch)
        else:
            def flush(batch):
                n = batch.batch_size()
                if n:
                    process_batch(batch, n)

            self._stream_native(files, parser, flush, BATCH_SIZE,
                                progress=progress_fn)
        nlines, nbad = parser.counters()
        if nlines:
            parser_stage.counters['ninputs'] = nlines
            parser_stage.counters['noutputs'] = nlines - nbad
            if nbad:
                parser_stage.counters['invalid json'] = nbad
        from . import byteparse as mod_byteparse
        mod_byteparse.publish_counters(parser_stage, parser)
        return scanners

    def _takeover_stream(self, files, parser, batch_size, progress,
                         new_executor, submit_batch, take_over,
                         device_batch):
        """The MT-host / device takeover state machine shared by scan
        and build: batches go to the MT executor until take_over()
        (auto mode's escalation decision) fires, then to the device
        scanner(s) via device_batch; a False from device_batch (lost
        probation) drains back to a fresh MT executor.  Batch order —
        and therefore the aggregator's insertion order — is preserved
        across both transitions: the executor is fully drained before
        any device batch flushes, and the device accumulator is flushed
        before the next executor starts."""
        state = {'ex': new_executor()}

        def flush(batch):
            n = batch.batch_size()
            if n == 0:
                return
            if state['ex'] is not None and take_over is not None and \
                    take_over():
                state['ex'].finish()
                state['ex'] = None
            if state['ex'] is None:
                if not device_batch(batch, n):
                    state['ex'] = new_executor()
                return
            submit_batch(state['ex'], batch, n)

        try:
            self._stream_native(files, parser, flush, batch_size,
                                progress=progress)
        finally:
            if state['ex'] is not None:
                state['ex'].finish()

    def _stream_native(self, files, parser, flush, batch_size,
                       progress=None):
        """Feed the concatenated file bytes to the parser and hand each
        batch to flush(batch) whenever enough records accumulate
        (partial trailing lines join across file boundaries — catstreams
        semantics).  The bulk of each read chunk is parsed in place
        (zero-copy span); only the carry-spanning line is stitched.

        A parser that can give its batch away (NativeParser.detach_batch)
        parses on a producer thread, one batch ahead: it fills batch
        N+1 while this thread runs flush on batch N.  One is in flush,
        one may wait in the queue and one is being filled.  The batch
        boundaries, their order and the single consumer are the serial
        loop's, and a batch sees the dictionaries as they stood at its
        end, so flush stages what it would have staged serially.  Other
        parsers (the byte lane) keep the serial loop: flush reads the
        parser itself, which is reset afterwards.

        progress(bytes_done, bytes_total), when given, is called before
        each flush with the bytes read when the batch ended — auto
        mode's device-switch heuristic estimates remaining work from it
        (total is 0 when sizes are unknowable, e.g. character
        devices).

        The request's open `scan.init` leaf ends here, where the
        first batch is asked for."""
        # larger reads amortize the multithreaded parse's fork/join; the
        # cap bounds how far a batch can overshoot the flush threshold
        # (flush is only checked between reads).  DN_READ_SIZE overrides
        # (testing / IO tuning).
        readsz = min(1 << 24, (1 << 22) * getattr(parser, 'nthreads', 1))
        try:
            readsz = int(os.environ.get('DN_READ_SIZE', 0)) or readsz
        except ValueError:
            pass
        total = 0
        for path, st in files:
            sz = getattr(st, 'st_size', 0) if st is not None else 0
            total += sz if sz and sz > 0 else 0

        if getattr(parser, 'detach_batch', None) is None:
            obs_metrics.leaf_stage.end_open('scan.init')
            for done in _parse_batches(files, parser, batch_size,
                                       readsz):
                if progress is not None:
                    progress(done, total)
                flush(parser)
                parser.reset_batch()
            return

        def detached(cancelled):
            # on the producer thread; the last batch may be empty (the
            # serial loop's last flush is a no-op then, and its
            # progress call is not)
            for done in _parse_batches(files, parser, batch_size,
                                       readsz, cancelled):
                yield (parser.detach_batch()
                       if parser.batch_size() else None), done

        ahead = _RunAhead(detached, name='dn-parse-ahead',
                          drop=_release_batch, join=True)
        obs_metrics.leaf_stage.end_open('scan.init')
        try:
            while True:
                # scan.parse_wait: this thread's wait for the producer's
                # next batch (none when the parse was wholly hidden)
                ready = ahead.ready()
                with obs_metrics.leaf_stage('scan.parse_wait'):
                    item = next(ahead, None)
                if item is None:
                    return
                batch, done = item
                if progress is not None:
                    progress(done, total)
                if batch is None:
                    continue
                obs_metrics.inc('scan_batches_handed')
                if ready:
                    obs_metrics.inc('scan_batches_ready')
                try:
                    flush(batch)
                finally:
                    batch.release()
        finally:
            ahead.close()

    def _index_write(self, metrics, interval, tagged_points):
        """Write tagged aggregated points into interval-chunked index
        files via the bulk write path; each file is written atomically
        and failures leave no tmp litter.  (reference:
        lib/datasource-file.js:444-547; the build path itself hands
        columnar blocks straight to index_build_mt.write_index_blocks)"""
        from . import index_build_mt as mod_ibmt
        writer = mod_ibmt.StreamingIndexWriter(metrics, interval,
                                               self.ds_indexpath)
        try:
            writer.write_points(tagged_points)
            writer.finish()
        except BaseException:
            writer.abort()
            raise

    # how many stdin points index_read routes to the sinks at a time:
    # large enough to amortize the bulk write, small enough that peak
    # memory stays flat however long the piped stream is
    INDEX_READ_CHUNK = 4096

    def index_read(self, metrics, interval, instream):
        """Read tagged json-skinner points (from stdin) and write index
        files, streaming in bounded chunks — the old path materialized
        the whole stream (bytes AND point dicts) before writing.
        (reference: lib/datasource-file.js:729-746)"""
        error = self.check_index_args(interval, True, False)
        if error is not None:
            raise error
        pipeline = Pipeline()
        from . import index_build_mt as mod_ibmt
        from . import resources as mod_resources
        writer = mod_ibmt.StreamingIndexWriter(metrics, interval,
                                               self.ds_indexpath)
        with mod_resources.translate_pressure_errors('index-read'):
            try:
                chunk = []
                for rec in mod_ingest.iter_records(
                        mod_ingest.iter_stream_lines(instream),
                        'json-skinner', pipeline):
                    chunk.append(rec)
                    if len(chunk) >= self.INDEX_READ_CHUNK:
                        writer.write_points(chunk)
                        chunk = []
                if chunk:
                    writer.write_points(chunk)
                writer.finish()
            except BaseException:
                writer.abort()
                raise
        return ScanResult(pipeline)

    # -- query ------------------------------------------------------------

    def index_find_params(self, interval, time_after, time_before):
        """(reference: lib/dragnet-impl.js:194-236)"""
        if interval == 'day':
            return (os.path.join(self.ds_indexpath, 'by_day'),
                    '%Y-%m-%d.sqlite', time_after, time_before)
        if interval == 'hour':
            return (os.path.join(self.ds_indexpath, 'by_hour'),
                    '%Y-%m-%d-%H.sqlite', time_after, time_before)
        if interval == 'all':
            return (os.path.join(self.ds_indexpath, 'all'), None, None,
                    None)
        return DNError('unsupported interval: "%s"' % interval)

    def _cached_index_walk(self, snap, timeformat, after, before,
                           pipeline):
        """The index-tree walk answered from the directory's snapshot
        (index_query_mt.TreeSnapshot): the whole tree for an unbounded
        query, the window's names for a bounded one, None where the
        snapshot cannot answer and _find must — the cluster backend
        overrides this to partition the listing across processes, the
        same way its _find override partitions fresh walks."""
        if before is None:
            return snap.whole_walk(pipeline)
        return snap.bounded_walk(timeformat, after, before, pipeline)

    def index_query_paths(self, query, interval, pipeline):
        """Enumerate the shard files an index query over `query` x
        `interval` would read: argument checks, the crash-recovery
        sweep, the (possibly memoized) tree walk, and the
        journal/tmp/quarantine litter filter — everything up to (not
        including) time-range pruning.  Returns (root, timeformat,
        files) with files as (path, statbuf) pairs in find order.
        Shared by query() below and the cluster partial-query
        executor (serve/router.py), so a member's partition-filtered
        shard set is drawn from the IDENTICAL walk a single-process
        query performs."""
        return self._index_query_walk(query, interval, pipeline)[:3]

    def _index_query_walk(self, query, interval, pipeline):
        """index_query_paths, and with its triple the directory
        snapshot that answered the walk (None where _find did):
        query() reads the pruned count off the same listing."""
        error = self.check_time_args(query.qc_after, query.qc_before)
        if error is None:
            error = self.check_index_args(interval, True, False)
        if error is not None:
            raise error

        params = self.index_find_params(interval or 'all', query.qc_after,
                                        query.qc_before)
        if isinstance(params, DNError):
            raise params
        root, timeformat, after, before = params

        # crash-recovery sweep (TTL-throttled): a builder that died
        # mid-publish must be rolled forward/back before this reader
        # walks the tree (index_journal)
        from . import index_journal as mod_journal
        mod_journal.maybe_sweep(self.ds_indexpath)

        # the tree's listing is kept under the directory's stat
        # identity (one os.stat a query proves it current) and the
        # walk's stage counters replay byte-identically; warn_func
        # consumers, and a window the snapshot cannot answer, take the
        # real walk
        from . import index_query_mt as mod_iqmt
        snap = files = None
        if pipeline.warn_func is None:
            snap = mod_iqmt.tree_snapshot(root)
        if snap is not None:
            files = self._cached_index_walk(snap, timeformat, after,
                                            before, pipeline)
        if files is None:
            snap = None
            files = self._find(root, timeformat, after, before, pipeline,
                               skip=mod_journal.is_index_litter)
        if isinstance(files, DNError):
            raise files
        # never open build machinery as a shard: journals, in-flight
        # tmps (a concurrent builder's), and the quarantine directory
        # stay out of the shard set (the snapshot's bounded walk names
        # base shards only: its layout left the litter out)
        if snap is None or before is None:
            files = [(p, st) for p, st in files
                     if not mod_journal.is_index_litter(p)]
        if timeformat is not None:
            # follow --append mini-generations: bounded finds
            # enumerate exact in-window filenames and can never name
            # a `<shard>.sqlite-gNNNNNN`; splice existing generations
            # in after their bases (unbounded walks see them
            # naturally)
            if snap is not None:
                files = snap.splice_generations(files)
            else:
                from . import rollup as mod_rollup
                files = mod_rollup.augment_generation_files(root, files)
        return root, timeformat, files, snap

    def _prune_index_paths(self, root, timeformat, files, snap, query):
        """(paths, npruned) of a walk's files: the shards whose
        filename window meets the query's, and how many of the tree's
        do not."""
        from . import index_query_mt as mod_iqmt
        paths = [p for p, st in files]
        if snap is not None:
            # the snapshot's walk named the window's shards and no
            # other: every one is kept
            return paths, snap.count_pruned(
                timeformat, query.qc_after, query.qc_before)
        paths, npruned = mod_iqmt.prune_shards(
            paths, timeformat, query.qc_after, query.qc_before)
        # time-bounded finds never enumerate out-of-window shards,
        # so count the tree's skipped files for the pruned counter
        # (the found list can only re-prune what enumeration
        # missed)
        return paths, max(npruned, mod_iqmt.count_pruned_shards(
            root, timeformat, query.qc_after, query.qc_before))

    def query(self, query, interval, dry_run=False):
        """Query the indexes.  (reference:
        lib/datasource-file.js:573-691)"""
        pipeline = Pipeline()
        # the query's plan, a leaf a part: the shard walk
        # (index_query.paths); the pruning and the integrity check
        # (index_query.prune); the rollup planner (index_query.plan)
        with obs_metrics.leaf_stage('index_query.paths'):
            root, timeformat, files, snap = self._index_query_walk(
                query, interval, pipeline)

        if dry_run:
            return ScanResult(pipeline,
                              dry_run_files=[p for p, st in files])

        index_list = pipeline.stage('Index List')
        aggr = Aggregator(query,
                          stage=pipeline.stage('Index Result Aggregator'))

        # Shard fan-out (index_query_mt): time-range pruning by shard
        # filename, then a DN_IQ_THREADS worker pool over the shard
        # handle cache, merged in find order — byte-identical to the
        # sequential loop (the reference's vasync barrier merged the
        # same way, lib/datasource-file.js:629-689).
        from . import index_query_mt as mod_iqmt
        from . import integrity as mod_integrity
        from . import rollup as mod_rollup
        with obs_metrics.leaf_stage('index_query.prune'):
            paths, npruned = self._prune_index_paths(
                root, timeformat, files, snap, query)
            if npruned:
                index_list.bump_hidden('index shards pruned', npruned)
            index_list.bump_hidden('index shards queried', len(paths))

            # verified reads (integrity.py): a catalogued shard that is
            # MISSING from the walk (quarantined after a corrupt
            # detect, or externally deleted) must degrade explicitly —
            # a clean retryable error naming the shard — never
            # silently short result bytes
            if mod_integrity.verify_mode() != 'off':
                mod_integrity.check_missing(
                    self.ds_indexpath, paths,
                    subdir=os.path.basename(root)
                    if timeformat is not None else None,
                    timeformat=timeformat, after_ms=query.qc_after,
                    before_ms=query.qc_before)

        # Query planner (rollup.py), a leaf of its own
        # (index_query.plan): serve from the coarsest covering rollup
        # shards and fold follow mini-generations into their logical
        # base shard.  It is handed the snapshot that answered the
        # walk: under it a level's manifest stays parsed and a
        # rollup's verdict stays kept for the stat TTL, and with none
        # (or a cold process) every fine source a rollup vouches for
        # is re-statted.  plan_query returns None whenever the walk
        # is plain per-file shards.
        with obs_metrics.leaf_stage('index_query.plan'):
            plan = mod_rollup.plan_query(self.ds_indexpath,
                                         interval or 'all', paths, query,
                                         snap=snap)

        nworkers = mod_iqmt.iq_threads()
        LOG.debug('query start', indexroot=root, nindexes=len(paths),
                  npruned=npruned, nworkers=nworkers,
                  interval=interval)

        aggr_stage = aggr.stage

        def merge(items):
            # per-shard aggregates arrive as key items (the
            # Aggregator wire format) in emission order: write_key
            # replays them byte-identically to re-writing the
            # shard's points.  Counter parity with the per-point
            # write() loop: one Index List input/output and one
            # aggregator-stage input per point, bumped in bulk.
            npts = len(items)
            if npts == 0:
                return
            index_list.bump('ninputs', npts)
            index_list.bump('noutputs', npts)
            aggr_stage.bump('ninputs', npts)
            aggr.merge_key_items(items)

        if plan is not None:
            # bump_hidden mirrors into the process-global store, so
            # `dn serve` /stats sees the fleet-wide coverage too
            index_list.bump_hidden('index shards via rollup',
                                   plan['ncovered'])
            index_list.bump_hidden('rollup shards queried',
                                   plan['nrollup'])

        # Stacked cross-shard execution (index_query_stack, default):
        # shard readers only LOAD matching column blocks, and one
        # vectorized filter+group-by over the concatenated batch
        # replaces the per-shard mask -> groupby -> merge loop —
        # byte-identical output (the stacked lexsort reproduces the
        # sequential insertion order exactly).  A plan's units enter
        # the same batch (a rollup shard's rows under the shard ids
        # of the fine buckets they stand for).  Falls back to the
        # per-shard loop (under a plan: rollup.execute_plan) when the
        # query shape or the exactness gate (non-integer weights)
        # demands it, or under DN_IQ_STACK=0.
        from . import index_query_stack as mod_iqs
        mod_iqs.run_index_query(paths, query, aggr, index_list,
                                nworkers, merge, plan=plan)

        return ScanResult(pipeline, points=_emit_points(aggr),
                          query=query)


class _RunAhead(object):
    """Iterate `make_items(cancelled)` on a producer thread of its own,
    one item ahead of the consumer (a queue of depth 1), under the
    consumer's request scope, so that the producer's stages and
    counters land in the request's registry and span tree.  Producer
    exceptions re-raise at the consumer.

    close() stops the producer: `cancelled()` turns true for the
    generator, which is closed on its own thread, and an item that was
    never taken goes to `drop`.  With `join`, close() also waits for
    the thread: for a producer whose every wait ends when it is
    stopped (one that consumes another _RunAhead passes its own
    `cancelled` down), not for one that may sit in a read."""

    _DONE = object()

    def __init__(self, make_items, name, drop=None, join=False,
                 cancelled=None):
        from . import vpipe as mod_vpipe
        self._q = mod_queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._drop = drop
        self._join = join
        self._cancelled = cancelled
        self._thread = threading.Thread(
            target=self._produce,
            args=(make_items, mod_vpipe.current_scope()),
            name=name, daemon=True)
        self._thread.start()

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except mod_queue.Full:
                continue
        return False

    def _produce(self, make_items, scope):
        from . import vpipe as mod_vpipe
        with mod_vpipe.adopt_scope(scope):
            items = make_items(self._stop.is_set)
            try:
                for item in items:
                    if not self._put(item):
                        self._dropped(item)
                        return
                self._put(self._DONE)
            except BaseException as e:     # re-raised by the consumer
                self._put(e)
            finally:
                items.close()

    def _dropped(self, item):
        if self._drop is not None and item is not self._DONE and \
                not isinstance(item, BaseException):
            self._drop(item)

    def ready(self):
        """True when the next item is waiting already."""
        return not self._q.empty()

    def __iter__(self):
        return self

    def __next__(self):
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=0.1)
            except mod_queue.Empty:
                if self._cancelled is not None and self._cancelled():
                    break
                continue
            if item is self._DONE:
                break
            if isinstance(item, BaseException):
                self.close()
                raise item
            return item
        self.close()
        raise StopIteration

    def close(self):
        self._stop.set()
        if self._join:
            self._thread.join()
        try:
            self._dropped(self._q.get_nowait())
        except mod_queue.Empty:
            pass


def _read_ahead(files, readsz, cancelled=None):
    """The concatenated chunk stream of `files` with a producer thread
    reading one chunk ahead (so file IO overlaps parse and engine work
    while at most ~2 chunks are resident).  Bytes come through
    ingest.open_byte_source — the pluggable fetcher seam.  Producer
    exceptions (unreadable file mid-stream) re-raise at the consumer;
    `cancelled()`, when given, ends the consumer's wait for a chunk."""
    def chunks(_cancelled):
        for path, st in files:
            for chunk in mod_ingest.open_byte_source(path, readsz):
                yield chunk

    return _RunAhead(chunks, name='dn-read-ahead', cancelled=cancelled)


def _parse_batches(files, parser, batch_size, readsz, cancelled=None):
    """Parse the chunk stream of `files` into `parser`, yielding the
    bytes read so far each time its batch is due for a flush: after a
    chunk that took it to `batch_size` records, and once at the end
    (when the batch may be empty).  The caller takes the batch before
    it resumes the generator.  `cancelled()`, when given, ends the
    stream early (the consumer of the batches has stopped)."""
    parse_at = getattr(parser, 'parse_at', None)
    done = 0

    def counted_chunks():
        # scan.read: the wait of this thread for the read-ahead
        # thread's next chunk
        chunks = _read_ahead(files, readsz, cancelled)
        try:
            while True:
                with obs_metrics.leaf_stage('scan.read'):
                    chunk = next(chunks, None)
                if chunk is None:
                    return
                yield chunk
        finally:
            chunks.close()

    def parse(buf, length=None):
        # scan.parse: the parser (all its threads) as this thread
        # sees it, over bytes or over (address, length)
        with obs_metrics.leaf_stage('scan.parse'):
            nrecords = parser.parse(buf) if length is None \
                else parse_at(buf, length)
        obs_metrics.inc('scan_parse_bytes',
                        len(buf) if length is None else length)
        obs_metrics.inc('scan_parse_records', nrecords)

    if parse_at is None:
        # byte-lane / plain parsers: complete-line buffers from
        # the shared chunk-boundary joiner (ingest.py — the same
        # carry discipline as iter_lines/iter_stream_lines)
        def counting(chunks):
            nonlocal done
            for chunk in chunks:
                done += len(chunk)
                yield chunk
        for lbuf in mod_ingest.iter_line_buffers(
                counting(counted_chunks())):
            parse(lbuf)
            if parser.batch_size() >= batch_size:
                yield done
        yield done
        return

    carry = b''
    for chunk in counted_chunks():
        done += len(chunk)
        nl = chunk.rfind(b'\n')
        if nl == -1:
            carry += chunk
            continue
        start = 0
        if carry:
            first = chunk.index(b'\n', 0, nl + 1)
            parse(carry + chunk[:first + 1])
            start = first + 1
        arr = np.frombuffer(chunk, dtype=np.uint8)
        if nl + 1 > start:
            parse(arr[start:].ctypes.data, nl + 1 - start)
        carry = chunk[nl + 1:]
        if parser.batch_size() >= batch_size:
            yield done
    if carry:
        parse(carry)
    yield done


def _release_batch(item):
    batch, _done = item
    if batch is not None:
        batch.release()


def _bump_parse_counters(parser_stage, adapter_stage, nlines, nbad, n):
    """Parse-layer counters (totals are monotonic; assigned, not
    accumulated) plus the per-batch adapter bumps."""
    parser_stage.counters['ninputs'] = nlines
    parser_stage.counters['noutputs'] = nlines - nbad
    if nbad:
        parser_stage.counters['invalid json'] = nbad
    if adapter_stage is not None and n:
        adapter_stage.bump('ninputs', n)
        adapter_stage.bump('noutputs', n)


def _batch_weights(skinner, src, n):
    """Per-record weights for one batch: 1 for raw json, the coerced
    point value for json-skinner (src is a parser or snapshot).  The
    first part of a batch's scan.stage; DeviceScan._stage_device is
    the rest."""
    with obs_metrics.leaf_stage('scan.stage'):
        if skinner:
            tags, nums, strcodes = src.columns('value')
            return _skinner_weights(tags, nums, strcodes, src)
        return np.ones(n, dtype=np.float64)


def _skinner_weights(tags, nums, strcodes, parser):
    """json-skinner point weights with JS Number coercion (NaN -> 0),
    matching engine.weights_array on the Python ingest path."""
    from . import native as mod_native
    from . import jsvalues as jsv
    weights = np.zeros(len(tags), dtype=np.float64)
    m = (tags == mod_native.TAG_INT) | (tags == mod_native.TAG_NUMBER)
    weights[m] = nums[m]
    weights[tags == mod_native.TAG_TRUE] = 1.0
    ms = tags == mod_native.TAG_STRING
    if ms.any():
        d = parser.dictionary('value')
        table = np.array(
            [0.0 if (f := jsv.to_number(s)) != f else f for s in d],
            dtype=np.float64)
        weights[ms] = table[strcodes[ms]]
    return weights


def _remapped(src, remap):
    """`src` (a parser, batch or snapshot) under the engine's field
    names: itself, or a _RemappedParser when the projection paths were
    prefixed (`remap` is None otherwise)."""
    return src if remap is None else _RemappedParser(src, remap)


class _RemappedParser(object):
    """Presents a NativeParser whose projection paths were prefixed
    (json-skinner: fields.*) under the engine's unprefixed names."""

    def __init__(self, parser, remap):
        self.parser = parser
        self.remap = remap
        # alias the wrapped source's decoded-array cache so per-batch
        # wrappers don't defeat it (the engine caches on the
        # provider's parser attribute)
        cache = getattr(parser, '_array_cache', None)
        if cache is None:
            cache = parser._array_cache = {}
        self._array_cache = cache

    def batch_size(self):
        return self.parser.batch_size()

    def columns(self, path):
        return self.parser.columns(self.remap[path])

    def date_columns(self, path):
        return self.parser.date_columns(self.remap[path])

    def dictionary(self, path):
        return self.parser.dictionary(self.remap[path])

    # one-pass batch stats (device-path eligibility); absent on
    # snapshot sources — callers feature-test with getattr
    def field_stats(self, path):
        fn = getattr(self.parser, 'field_stats', None)
        return None if fn is None else fn(self.remap[path])

    def nums_i32(self, path):
        return self.parser.nums_i32(self.remap[path])

    def date_stats(self, path):
        fn = getattr(self.parser, 'date_stats', None)
        return None if fn is None else fn(self.remap[path])

    def date_i32(self, path):
        return self.parser.date_i32(self.remap[path])

    def date_err(self, path):
        return self.parser.date_err(self.remap[path])

    def tags_col(self, path):
        return self.parser.tags_col(self.remap[path])

    def strcodes_col(self, path):
        return self.parser.strcodes_col(self.remap[path])


