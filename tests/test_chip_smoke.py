"""chip_smoke.py rehearsed on XLA:CPU: every phase passes its byte
comparison and its engagement check, and the verdict is `ok: false` for
the single reason that the platform is not `tpu`; with the native
parser disabled the forced scans FAIL rather than answer from the
host.  And the hand-kept copies of the queries and the metrics
(chip_smoke.py's `dn` arguments, the benchmark's JSON files) say what
bench.py's say."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench                                        # noqa: E402
import chip_smoke                                   # noqa: E402
from dragnet_tpu import cli                         # noqa: E402
from dragnet_tpu import query as mod_query          # noqa: E402

PHASES = ('scan-dense', 'scan-pallas', 'scan-sparse', 'build', 'query',
          'auto')


def _query(doc):
    qc = mod_query.query_load(dict(doc))
    return qc.qc_breakdowns, qc.qc_filter


def _scan_args_query(args):
    """`dn scan ARGS ds`, as cmd_scan loads it."""
    opts = cli.dn_parse_args(list(args) + ['ds'],
                             ['before', 'after', 'filter', 'breakdowns'])
    return _query(cli.dn_query_doc(opts))


def _metric(doc):
    return mod_query.metric_serialize(
        mod_query.metric_deserialize(doc), skip_datasource=True)


def _metric_args_metric(name, args):
    """`dn metric-add ARGS ds NAME`, as cmd_metric_add stores it."""
    opts = cli.dn_parse_args(list(args) + ['ds', name],
                             ['breakdowns', 'filter'])
    return _metric({'name': name, 'filter': opts.filter or None,
                    'breakdowns': opts.breakdowns})


def _bench_json(*rel):
    with open(os.path.join(ROOT, 'benchmarks', *rel)) as f:
        return json.load(f)


def _cell_query(cell='muskie-30d.scan-dense'):
    (template,) = _bench_json('workloads', cell + '.json')['templates']
    return _query(template['query'])


def _config_metrics():
    return [_metric(m) for m in
            _bench_json('configs', 'muskie-30d.json')['metrics']]


@pytest.mark.parametrize('copy, original', [
    (lambda: _scan_args_query(chip_smoke.QUERY_ARGS),
     lambda: _query(bench.QUERY)),
    (lambda: _scan_args_query(chip_smoke.PALLAS_ARGS),
     lambda: _query(bench.PALLAS_QUERY)),
    (lambda: _metric_args_metric(*chip_smoke.METRIC_ARGS[0]),
     lambda: _metric(bench.METRICS[0])),
    (lambda: _metric_args_metric(*chip_smoke.METRIC_ARGS[1]),
     lambda: _metric(bench.METRICS[1])),
    (lambda: _metric_args_metric(*chip_smoke.METRIC_ARGS[2]),
     lambda: _metric(bench.METRICS[2])),
    (_cell_query, lambda: _query(bench.QUERY)),
    (_config_metrics, lambda: [_metric(m) for m in bench.METRICS]),
    # the high-cardinality cells ask what the smoke's sparse phases ask
    (lambda: _cell_query('muskie-30d-highcard.scan-highcard'),
     lambda: _scan_args_query(chip_smoke.SPARSE_ARGS)),
    (lambda: _cell_query('muskie-30d-highcard-mesh4.scan-highcard'),
     lambda: _scan_args_query(chip_smoke.SPARSE_ARGS)),
], ids=['smoke-query', 'smoke-pallas-query', 'smoke-m1', 'smoke-m2',
        'smoke-m3', 'cell-scan-dense-query', 'config-muskie-30d-metrics',
        'cell-scan-highcard-query', 'cell-mesh4-scan-highcard-query'])
def test_the_copies_agree_with_bench(copy, original):
    assert len(chip_smoke.METRIC_ARGS) == len(bench.METRICS) == 3
    assert copy() == original()


def _run(extra_env, *args):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)      # one CPU device, as on one chip
    env.update(extra_env)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'chip_smoke.py'),
         '--records', '5000'] + list(args),
        env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=600)
    lines = p.stdout.decode('utf-8', 'replace').splitlines()
    return p.returncode, lines


def _phase_lines(lines):
    got = {}
    for line in lines:
        if line.startswith('phase '):
            name, _, rest = line[len('phase '):].partition(': ')
            got[name] = rest
    return got


def test_rehearsal_passes_every_phase_and_refuses_the_cpu():
    rc, lines = _run({})
    phases = _phase_lines(lines)
    assert tuple(phases) == PHASES, '\n'.join(lines)
    for name, rest in phases.items():
        assert rest.startswith('passed '), '\n'.join(lines)
    assert rc != 0
    verdict = json.loads(lines[-1])
    assert verdict == {'ok': False, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': 1}}
    assert "not ok: platform is 'cpu', not tpu" in lines


def test_rehearsal_of_four_chips_passes_both_mesh_phases():
    """`--chips 4` on four virtual CPU devices: the dense scan and the
    high-cardinality one through the cluster backend, each equal to
    its one-chip scan, each with its kernel and its merge on every
    record."""
    rc, lines = _run(
        {'XLA_FLAGS': '--xla_force_host_platform_device_count=4'},
        '--chips', '4')
    phases = _phase_lines(lines)
    assert tuple(phases) == ('mesh-scan', 'mesh-scan-sparse'), \
        '\n'.join(lines)
    for name, rest in phases.items():
        assert rest.startswith('passed '), '\n'.join(lines)
    assert 'kernel=segment-sum merge=psum+pmin' in phases['mesh-scan']
    assert 'kernel=sparse-sort-merge merge=allgather+sparse-fold' in \
        phases['mesh-scan-sparse']
    assert rc != 0
    assert json.loads(lines[-1]) == {'ok': False, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': 4}}


def test_forced_scans_fail_without_the_native_parser():
    rc, lines = _run({'DN_NATIVE': '0'})
    phases = _phase_lines(lines)
    # (scan-pallas projects top-level fields only, so the byte-parse
    # lane still feeds the device columns without the native library)
    for name in ('scan-dense', 'scan-sparse', 'build'):
        assert phases[name].startswith('FAILED'), '\n'.join(lines)
    assert any('native column parser' in ln for ln in lines)
    assert rc != 0
    assert json.loads(lines[-1])['ok'] is False
