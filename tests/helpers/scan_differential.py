"""Shared engine-differential scan helper: run a DatasourceFile scan
with a pinned engine and small batches, returning (points, counters)
with engine telemetry ('ndevicebatches' & co.) excluded from the
counter-parity set."""


def scan_points_counters(monkeypatch, datafile, qconf, engine,
                         batch=None, read_size=None, fmt='json',
                         time_field=None, ds_filter=None,
                         scan_threads='0'):
    from dragnet_tpu import query as mod_query
    from dragnet_tpu.datasource_file import DatasourceFile

    monkeypatch.setenv('DN_ENGINE', engine)
    monkeypatch.setenv('DN_NATIVE', '1')
    monkeypatch.setenv('DN_SCAN_THREADS', scan_threads)
    if read_size is not None:
        monkeypatch.setenv('DN_READ_SIZE', str(read_size))
    if batch is not None:
        from dragnet_tpu import engine as mod_engine
        from dragnet_tpu import device_scan as mod_ds
        monkeypatch.setattr(mod_engine, 'BATCH_SIZE', batch)
        monkeypatch.setattr(mod_ds, 'BATCH_SIZE', batch)
    bc = {'path': datafile}
    if time_field is not None:
        bc['timeField'] = time_field
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': bc,
        'ds_filter': ds_filter,
        'ds_format': fmt,
    })
    r = ds.scan(mod_query.query_load(dict(qconf)))
    counters = {(s.name, k): v for s in r.pipeline.stages
                for k, v in s.counters.items()
                if v and k not in s.hidden}
    return r.points, counters


def serial_loop(monkeypatch):
    """Take the native parser's batch hand-off away, so that
    `_stream_native` runs its serial loop over the same parser (it
    chooses by what the parser object offers): the reference the
    producer thread is held to, byte for byte."""
    from dragnet_tpu import native as mod_native
    monkeypatch.setattr(mod_native.NativeParser, 'detach_batch', None)


def batches_handed():
    """Batches that have crossed the hand-off in this process
    (`scan_batches_handed` of the global registry)."""
    from dragnet_tpu.obs import metrics as obs_metrics
    return obs_metrics.global_registry().counter(
        'scan_batches_handed').value


# how a corpus of lines lies on disk; every layout is the same byte
# stream to a scan but for 'no-final-newline' (catstreams semantics)
LAYOUTS = ('one-file', 'no-final-newline', 'line-spans-files',
           'empty-file-between')


def write_layout(root, lines, layout):
    """Write `lines` under `root` in one of LAYOUTS; returns the path
    for the datasource (a file, or a directory whose files sort in
    stream order)."""
    import os
    data = ('\n'.join(lines) + '\n').encode()
    if layout == 'one-file':
        parts = None
    elif layout == 'no-final-newline':
        data, parts = data[:-1], None
    elif layout == 'line-spans-files':
        # the first file ends in the middle of a line, the second
        # begins with the rest of it
        cut = len(data) // 2
        while data[cut - 1:cut] == b'\n' or data[cut:cut + 1] == b'\n':
            cut += 1
        parts = [data[:cut], data[cut:]]
    elif layout == 'empty-file-between':
        cut = data.index(b'\n', len(data) // 3) + 1
        parts = [data[:cut], b'', data[cut:]]
    else:
        raise ValueError(layout)
    if parts is None:
        path = os.path.join(str(root), 'corpus.log')
        with open(path, 'wb') as f:
            f.write(data)
        return path
    path = os.path.join(str(root), 'corpus')
    os.mkdir(path)
    for i, part in enumerate(parts):
        with open(os.path.join(path, '%02d.log' % i), 'wb') as f:
            f.write(part)
    return path
