"""Hardware-gated real-cluster test: when a real multi-chip rig is
available the 2-process cluster assertions run on actual TPU devices;
otherwise the test skips cleanly.  This is the reference's discipline
for its Manta-backed distributed tests, which env-gate on a real Manta
and exit 2 (= skip) when absent
(/root/reference/tests/dn/manta/tst.scan_manta.sh:26-30).

Enable with:

    DN_REAL_CLUSTER=1 python -m pytest tests/test_real_cluster.py

Knobs (all optional):

    DN_REAL_CLUSTER_NPROCS    number of processes (default 2)
    DN_REAL_CLUSTER_PLATFORM  JAX platform for workers (default 'tpu')
    DN_REAL_CLUSTER_COORD     coordinator address (default: a free
                              127.0.0.1 port — single-host rigs)
    DN_REAL_CLUSTER_NO_DEVICE_SPLIT=1
                              do not set TPU_VISIBLE_DEVICES per
                              process (set when the rig pre-partitions
                              chips, e.g. one process per host)

On a single-host multi-chip rig the default assigns chip i to process
i via TPU_VISIBLE_DEVICES, the standard way to run multi-process JAX
on one TPU host."""

import json
import os
import random
import socket
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      'helpers', 'cluster_worker.py')

DAYS = ('2014-05-01', '2014-05-02', '2014-05-03')

pytestmark = [pytest.mark.slow, pytest.mark.realcluster]


def _gate():
    if not os.environ.get('DN_REAL_CLUSTER'):
        pytest.skip('DN_REAL_CLUSTER not set: no real multi-chip rig '
                    '(one chip at most here); set DN_REAL_CLUSTER=1 '
                    'on a machine with >=2 TPU chips to run')


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_data(datadir):
    rng = random.Random(11)
    for fn in ('a.log', 'b.log'):
        with open(datadir / fn, 'w') as f:
            for _ in range(200):
                f.write(json.dumps({
                    'time': '%sT%02d:00:%02dZ'
                            % (rng.choice(DAYS), rng.randrange(24),
                               rng.randrange(60)),
                    'host': rng.choice(['x', 'y', 'z']),
                    'latency': rng.choice([1, 7, 90, 2500]),
                }) + '\n')


def _run_real_workers(args, timeout=600):
    """Launch the cluster worker on real chips: JAX_PLATFORMS=tpu (not
    the CPU mesh the rest of the suite forces), one process per chip
    unless the rig pre-partitions them."""
    nprocs = int(os.environ.get('DN_REAL_CLUSTER_NPROCS', '2'))
    platform = os.environ.get('DN_REAL_CLUSTER_PLATFORM', 'tpu')
    coord = os.environ.get('DN_REAL_CLUSTER_COORD',
                           '127.0.0.1:%d' % _free_port())
    env = dict(os.environ)
    # the suite conftest forces the virtual CPU mesh; undo for workers
    env.pop('XLA_FLAGS', None)
    env.update({
        'DN_COORDINATOR': coord,
        'DN_NUM_PROCESSES': str(nprocs),
        'JAX_PLATFORMS': platform,
    })
    procs = []
    for pid in range(nprocs):
        e = dict(env, DN_PROCESS_ID=str(pid))
        if not os.environ.get('DN_REAL_CLUSTER_NO_DEVICE_SPLIT'):
            e['TPU_VISIBLE_DEVICES'] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER] + args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=e))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail('real-cluster worker hung')
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, err.decode()[-2000:]
    return [json.loads(out.decode().strip().splitlines()[-1])
            for rc, out, err in outs]


def _file_ds(datadir, indexdir=None):
    from dragnet_tpu import datasource_file
    bc = {'path': str(datadir), 'timeField': 'time'}
    if indexdir is not None:
        bc['indexPath'] = str(indexdir)
    return datasource_file.DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': bc,
        'ds_filter': None, 'ds_format': 'json',
    })


def _query_conf():
    from dragnet_tpu import query as mod_query
    return mod_query.query_load({'breakdowns': [
        {'name': 'host'}, {'name': 'latency', 'aggr': 'quantize'}]})


def test_real_cluster_scan(tmp_path):
    """Distributed scan on real chips must equal the single-process
    host result exactly (same assertion as the CPU-mesh suite)."""
    _gate()
    datadir = tmp_path / 'data'
    datadir.mkdir()
    _write_data(datadir)

    results = _run_real_workers(['scan', str(datadir)])
    expected = [[f, v] for f, v in
                _file_ds(datadir).scan(_query_conf()).points]
    for r in results:
        assert sorted(map(json.dumps, r['points'])) == \
            sorted(map(json.dumps, expected))


def test_real_cluster_build(tmp_path):
    """Distributed build on real chips: index shards byte-identical to
    a single-process build."""
    _gate()
    datadir = tmp_path / 'data'
    datadir.mkdir()
    _write_data(datadir)
    idx_multi = tmp_path / 'idx_multi'
    idx_single = tmp_path / 'idx_single'

    results = _run_real_workers(['build', str(datadir), str(idx_multi)])
    built = results[0]['built']
    for r in results:
        assert r['built'] == built
    assert len(built) == len(DAYS)

    from dragnet_tpu import query as mod_query
    import importlib.util
    spec = importlib.util.spec_from_file_location('cw', WORKER)
    cw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cw)
    metric = mod_query.metric_deserialize(cw.METRIC)
    _file_ds(datadir, idx_single).build([metric], 'day')

    for rel in built:
        with open(idx_multi / rel, 'rb') as f:
            multi_bytes = f.read()
        with open(idx_single / rel, 'rb') as f:
            single_bytes = f.read()
        assert multi_bytes == single_bytes, \
            'index shard %s differs on real cluster' % rel
