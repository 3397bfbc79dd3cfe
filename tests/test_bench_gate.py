"""bench.py's device-alive gate: a device backend that never answers
(every op hanging) must cost one bounded probe, not a hung
benchmark."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench                                   # noqa: E402
from dragnet_tpu import ops                    # noqa: E402


def test_device_alive_times_out_on_hang(monkeypatch):
    def hang():
        time.sleep(300)
    monkeypatch.setattr(ops, 'backend_ready', hang)
    t0 = time.monotonic()
    assert bench.device_alive(timeout_s=1) is False
    assert time.monotonic() - t0 < 10


def test_device_alive_false_on_error(monkeypatch):
    def boom():
        raise RuntimeError('no backend')
    monkeypatch.setattr(ops, 'backend_ready', boom)
    assert bench.device_alive(timeout_s=30) is False


def test_device_alive_true_on_working_backend():
    if ops.get_jax() is None or not ops.backend_ready():
        pytest.skip('jax unavailable')
    # the suite runs on the CPU backend (conftest): a real, working
    # device_put round trip
    assert bench.device_alive(timeout_s=180) is True


# -- wedge recovery: the subprocess re-exec retry --------------------------

def test_device_retry_parses_subprocess_result(monkeypatch):
    """A healthy re-exec'd subprocess recovers the device legs."""
    import json
    import subprocess
    payload = {'ok': True, 'device_large_records_per_sec': 123,
               'device_output_points': 4, 'device_batches': 7}

    class FakeProc(object):
        returncode = 0
        stdout = (json.dumps(payload) + '\n').encode()
        stderr = b''
    calls = []

    def fake_run(cmd, **kwargs):
        calls.append(cmd)
        return FakeProc()
    monkeypatch.setattr(subprocess, 'run', fake_run)
    res = bench.device_retry_subprocess('/tmp/x.log', 1000)
    assert res == payload
    assert '--device-legs' in calls[0]


def test_device_retry_null_on_still_wedged(monkeypatch):
    """A subprocess that also finds the backend dead (ok: false), or
    that fails outright, yields None — the caller records nulls only
    after the retry."""
    import subprocess

    class DeadProc(object):
        returncode = 0
        stdout = b'{"ok": false}\n'
        stderr = b''
    monkeypatch.setattr(subprocess, 'run',
                        lambda cmd, **kw: DeadProc())
    assert bench.device_retry_subprocess('/tmp/x.log', 1000) is None

    class BrokenProc(object):
        returncode = 3
        stdout = b''
        stderr = b'boom'
    monkeypatch.setattr(subprocess, 'run',
                        lambda cmd, **kw: BrokenProc())
    assert bench.device_retry_subprocess('/tmp/x.log', 1000) is None

    def timeout_run(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, 1)
    monkeypatch.setattr(subprocess, 'run', timeout_run)
    assert bench.device_retry_subprocess('/tmp/x.log', 1000) is None


# -- parse-lane legs: tier-1-safe smoke ------------------------------------

def test_parse_bench_extras_smoke(tmp_path, monkeypatch):
    """The parse-lane measurement runs on the CPU backend and records
    every lane's rate plus the fallback share."""
    datafile = str(tmp_path / 'parse.log')
    n = 8000
    bench.gen_to_file(n, datafile)
    monkeypatch.setenv('DN_BENCH_PARSE_BYTES', str(1 << 20))
    use_device = ops.get_jax() is not None
    out = bench.parse_bench_extras(datafile, n, use_device,
                                   end_to_end=True)
    assert out['parse_host_mb_per_sec'] > 0
    assert out['parse_vector_mb_per_sec'] > 0
    assert out['parse_vector_fallback_pct'] < 1.0
    assert out['parse_host_records_per_sec'] > 0
    assert out['parse_vector_records_per_sec'] > 0
    if use_device:
        assert out['parse_device_mb_per_sec'] > 0
        assert out['parse_device_records_per_sec'] > 0


# -- chaos observability: tier-1-safe smoke --------------------------------

def test_injection_counters_visible_under_counters_all(tmp_path,
                                                       monkeypatch):
    """The bench-gate contract for the fault subsystem: with DN_FAULTS
    armed, DN_COUNTERS_ALL=1 surfaces per-site injection counters in
    the --counters dump, and faults.stats() reports the same firing."""
    import io
    from dragnet_tpu import faults as mod_faults
    from dragnet_tpu import query as mod_query
    from dragnet_tpu.datasource_file import DatasourceFile

    datafile = str(tmp_path / 'd.log')
    bench.gen_to_file(2000, datafile)
    idx = str(tmp_path / 'idx')
    ds = DatasourceFile({
        'ds_backend': 'file', 'ds_format': 'json',
        'ds_backend_config': {'path': datafile, 'indexPath': idx,
                              'timeField': 'time'},
        'ds_filter': None})
    metric = mod_query.metric_deserialize({
        'name': 'm', 'datasource': 'd', 'filter': None,
        'breakdowns': [
            {'name': 'timestamp', 'field': 'time', 'date': 'time',
             'aggr': 'lquantize', 'step': 86400},
            {'name': 'host', 'field': 'host'}]})
    ds.build([metric], 'day')

    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:delay:1.0')
    monkeypatch.setenv('DN_FAULT_DELAY_MS', '1')
    monkeypatch.setenv('DN_COUNTERS_ALL', '1')
    mod_faults.reset()
    try:
        q = mod_query.query_load({'breakdowns': [
            {'name': 'host', 'field': 'host'}]})
        r = ds.query(q, 'day')
        out = io.StringIO()
        r.pipeline.dump_counters(out)
        assert 'faults injected' in out.getvalue()
        assert 'iq.shard_read:' in out.getvalue()
        st = mod_faults.stats()['iq.shard_read']
        assert st['fired'] > 0 and st['fired'] <= st['checked']
    finally:
        mod_faults.reset()


# -- serve legs: tier-1-safe smoke -----------------------------------------

def test_serve_bench_smoke(tmp_path, monkeypatch):
    """A miniature --serve-only run: cold CLI subprocess vs a real
    warm `dn serve` daemon, with the acceptance figures (warm p50 vs
    cold p50, byte-identical output, device_path_engaged from /stats)
    landing in the extras."""
    monkeypatch.setenv('DN_BENCH_SERVE_RECORDS', '4000')
    monkeypatch.setenv('DN_BENCH_SERVE_DAYS', '20')
    monkeypatch.setenv('DN_BENCH_SERVE_COLD_REPS', '1')
    monkeypatch.setenv('DN_BENCH_SERVE_WARM_REPS', '5')
    monkeypatch.setenv('DN_BENCH_SERVE_BURST', '4')
    sv = bench.serve_bench(str(tmp_path))
    assert sv['serve_shards'] == 20
    assert sv['serve_query_warm_p50_ms'] > 0
    assert sv['serve_query_cold_cli_p50_ms'] > 0
    # the acceptance bar: warm-server p50 at most half the cold CLI
    # process p50 (in practice the gap is orders of magnitude — the
    # cold side pays interpreter boot + imports per query)
    assert sv['serve_query_warm_p50_ms'] <= \
        0.5 * sv['serve_query_cold_cli_p50_ms']
    assert sv['serve_output_byte_identical'] is True
    assert sv['serve_coalesced_requests'] >= 0
    assert isinstance(sv['device_path_engaged'], bool)
    assert sv['serve_drained_clean'] is True


@pytest.mark.slow
def test_main_serve_emits_json_line(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv('DN_BENCH_SERVE_RECORDS', '4000')
    monkeypatch.setenv('DN_BENCH_SERVE_DAYS', '10')
    monkeypatch.setenv('DN_BENCH_SERVE_COLD_REPS', '1')
    monkeypatch.setenv('DN_BENCH_SERVE_WARM_REPS', '3')
    bench.main_serve()
    import json
    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc['metric'] == 'serve_query_warm_p50_ms'
    assert doc['value'] > 0
    assert 'device_path_engaged' in doc['extra']


@pytest.mark.slow
def test_main_parse_emits_json_line(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv('DN_BENCH_PARSE_RECORDS', '20000')
    monkeypatch.setenv('DN_BENCH_PARSE_BYTES', str(2 << 20))
    bench.main_parse()
    import json
    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc['metric'] == 'parse_vector_mb_per_sec'
    assert doc['value'] > 0
    assert 'parse_host_mb_per_sec' in doc['extra']
