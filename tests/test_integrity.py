"""End-to-end shard integrity (dragnet_tpu/integrity.py,
serve/scrub.py): the per-tree checksum catalog written through the
publish/recovery paths, DN_VERIFY verified reads (clean retryable
corrupt/missing errors, quarantine, handle-cache interplay), the
`flip` fault kind, `dn scrub` / `dn quarantine`, and cluster
self-healing repair (detect -> failover -> background re-fetch from a
co-replica, byte-identity restored)."""

import json
import os
import shutil
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                               # noqa: E402
from dragnet_tpu import faults as mod_faults              # noqa: E402
from dragnet_tpu import index_journal as mod_journal      # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt        # noqa: E402
from dragnet_tpu import integrity as mod_integrity        # noqa: E402
from dragnet_tpu import query as mod_query                # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile    # noqa: E402
from dragnet_tpu.errors import DNError                    # noqa: E402
from dragnet_tpu.serve import server as mod_server        # noqa: E402


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def _make_data(path, n=1500, days=5):
    import datetime
    t0 = 1388534400  # 2014-01-01T00:00:00Z
    with open(path, 'w') as f:
        for i in range(n):
            ts = datetime.datetime.utcfromtimestamp(
                t0 + (i * 4999) % (days * 86400)).strftime(
                    '%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({
                'time': ts, 'host': 'host%d' % (i % 4),
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


def _ds(datafile, idx):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile, 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})


def _metric():
    return mod_query.metric_deserialize({'name': 'm', 'breakdowns': [
        {'name': 'ts', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}]})


def _query(after=None, before=None):
    conf = {'breakdowns': [{'name': 'host'}]}
    if after is not None:
        conf['timeAfter'] = after
        conf['timeBefore'] = before
    q = mod_query.query_load(conf)
    assert not isinstance(q, DNError), q
    return q


def _flip_byte(path, off=None):
    size = os.path.getsize(path)
    off = size // 2 if off is None else off
    with open(path, 'r+b') as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0x5a]))


@pytest.fixture(autouse=True)
def fresh_state():
    mod_iqmt.shard_cache_clear()
    mod_integrity.reset_memo()
    mod_journal.reset_sweep_memo()
    yield
    mod_iqmt.shard_cache_clear()
    mod_integrity.reset_memo()


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """One built day-tree + its datasource, DN_VERIFY unset."""
    monkeypatch.delenv('DN_VERIFY', raising=False)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    return {'ds': ds, 'idx': idx, 'datafile': datafile}


# -- the catalog ------------------------------------------------------------


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
@pytest.mark.parametrize('interval', ['day', 'all'])
def test_publish_writes_catalog_matching_bytes(tmp_path, monkeypatch,
                                               index_format,
                                               interval):
    """Every build lands a `.dn_integrity.json` whose (size, crc32)
    entries match the committed shard bytes exactly, in both storage
    formats and tree shapes."""
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=600)
    _ds(datafile, idx).build([_metric()], interval)
    catalog = mod_integrity.load_catalog(idx)
    shards = dict(mod_integrity.iter_tree_shards(idx))
    assert sorted(catalog) == sorted(shards)
    assert len(catalog) >= 1
    for rel, path in shards.items():
        assert mod_integrity.file_crc(path) == catalog[rel], rel


def test_rebuild_and_catalog_litter_filtering(tree):
    """The catalog is filtered from shard walks (a query never opens
    it as a shard), rebuilds refresh its entries, and its tmp name is
    litter."""
    assert mod_journal.is_index_litter(mod_journal.INTEGRITY_NAME)
    assert mod_journal.is_index_litter(
        mod_journal.INTEGRITY_NAME + '.123.tmp')
    before = mod_integrity.load_catalog(tree['idx'])
    _make_data(tree['datafile'], n=2500)
    tree['ds'].build([_metric()], 'day')
    after = mod_integrity.load_catalog(tree['idx'])
    assert sorted(after) == sorted(before)
    assert after != before          # sizes/crcs moved with the data
    for rel, ent in after.items():
        path = os.path.join(tree['idx'], rel)
        assert mod_integrity.file_crc(path) == ent


def test_rollforward_recovery_updates_catalog(tmp_path):
    """The recovery sweep's roll-forward replays a dead build's
    commit-record checksums into the catalog — a recovered tree
    verifies like a cleanly published one."""
    idx = str(tmp_path / 'idx')
    os.makedirs(idx)
    final = os.path.join(idx, 'all')
    tmp = final + '.999999.1'
    with open(tmp, 'wb') as f:
        f.write(b'shard-bytes-here')
    size, crc = mod_integrity.file_crc(tmp)
    jpath = os.path.join(idx, mod_journal.JOURNAL_PREFIX +
                         '999999.1.json')
    with open(jpath, 'w') as f:
        json.dump({'pid': 999999, 'build_id': '999999.1',
                   'state': 'commit', 'time': 0,
                   'entries': [[tmp, final]],
                   'integrity': {idx: {'all': [size, crc]}}}, f)
    res = mod_journal.sweep_index_tree(idx)
    assert res['rollforwards'] == 1
    assert os.path.exists(final) and not os.path.exists(jpath)
    assert mod_integrity.load_catalog(idx) == {'all': (size, crc)}


# -- verified reads ---------------------------------------------------------


def test_verify_open_clean_tree_byte_identical(tree, monkeypatch):
    """DN_VERIFY=open on a clean tree returns byte-identical points
    and actually verifies (counter > 0)."""
    from dragnet_tpu import vpipe as mod_vpipe
    ref = tree['ds'].query(_query(), 'day').points
    mod_iqmt.shard_cache_clear()
    monkeypatch.setenv('DN_VERIFY', 'open')
    before = mod_vpipe.global_counters().get(
        'integrity reads verified', 0)
    got = tree['ds'].query(_query(), 'day').points
    assert got == ref
    assert mod_vpipe.global_counters().get(
        'integrity reads verified', 0) > before
    # warm cache: the second query pays no re-verification in open
    # mode (hits skip it; the counter holds still)
    during = mod_vpipe.global_counters().get(
        'integrity reads verified', 0)
    assert tree['ds'].query(_query(), 'day').points == ref
    assert mod_vpipe.global_counters().get(
        'integrity reads verified', 0) == during


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
def test_corrupt_detect_clean_error_and_quarantine(tmp_path,
                                                   monkeypatch,
                                                   index_format):
    """The mid-query corrupt-detect drill, both storage formats: a
    bit-flipped shard raises a clean retryable DNError NAMING the
    shard (never a traceback, never short bytes), the shard lands in
    `.dn_quarantine/`, and the catalog entry is kept (it is the
    repair target)."""
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=800)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    catalog = mod_integrity.load_catalog(idx)
    rel = sorted(catalog)[0]
    shard = os.path.join(idx, rel)
    _flip_byte(shard)
    monkeypatch.setenv('DN_VERIFY', 'open')
    with pytest.raises(DNError) as ei:
        ds.query(_query(), 'day')
    e = ei.value
    assert rel.split('/')[-1] in e.message
    assert 'integrity' in e.message
    assert getattr(e, 'retryable', False)
    assert getattr(e, 'corrupt_shard', None) == rel
    assert not os.path.exists(shard)
    qdir = os.path.join(idx, mod_journal.QUARANTINE_DIR)
    assert os.path.basename(rel) in os.listdir(qdir)
    assert mod_integrity.load_catalog(idx)[rel] == catalog[rel]
    # the follow-up: the walk no longer sees the shard, and the
    # missing-shard gate turns that into an explicit clean error
    # instead of silently short results
    with pytest.raises(DNError) as ei2:
        ds.query(_query(), 'day')
    assert 'missing on disk' in ei2.value.message
    assert getattr(ei2.value, 'retryable', False)
    # DN_VERIFY=off keeps the legacy short-read behavior untouched
    monkeypatch.setenv('DN_VERIFY', 'off')
    mod_integrity.reset_memo()
    assert ds.query(_query(), 'day').points  # serves what remains


def test_missing_gate_scoped_to_query_window(tree, monkeypatch):
    """A quarantined shard outside the query's time window must not
    fail bounded queries — the gate names only shards the walk would
    have served."""
    monkeypatch.setenv('DN_VERIFY', 'open')
    catalog = mod_integrity.load_catalog(tree['idx'])
    last = sorted(catalog)[-1]            # 2014-01-05
    os.unlink(os.path.join(tree['idx'], last))
    bounded = tree['ds'].query(
        _query(after='2014-01-01', before='2014-01-03'), 'day')
    assert bounded.points
    with pytest.raises(DNError) as ei:
        tree['ds'].query(_query(), 'day')
    assert last in ei.value.message or 'missing on disk' \
        in ei.value.message


def test_verify_full_catches_corruption_under_warm_cache(
        tree, monkeypatch):
    """open mode pays once per generation (a warm cache hit skips
    re-verification — corruption landing between leases goes unseen
    until the handle ages out); full mode re-verifies every lease and
    catches it immediately."""
    monkeypatch.setenv('DN_VERIFY', 'open')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '60000')
    ref = tree['ds'].query(_query(), 'day').points
    catalog = mod_integrity.load_catalog(tree['idx'])
    rel = sorted(catalog)[0]
    _flip_byte(os.path.join(tree['idx'], rel))
    # open + warm handles: the flipped bytes are NOT re-read (the
    # cache hit is the amortization contract)
    assert tree['ds'].query(_query(), 'day').points == ref
    monkeypatch.setenv('DN_VERIFY', 'full')
    with pytest.raises(DNError) as ei:
        tree['ds'].query(_query(), 'day')
    assert getattr(ei.value, 'corrupt_shard', None) == rel


def test_handle_leased_across_quarantine_not_recached(tree):
    """The handle-cache vs quarantine interplay: a shard handle
    leased BEFORE a corrupt-detect quarantine must not re-enter the
    cache at checkin (the per-path generation bump — same contract as
    the PR 5 invalidate_index_tree tests)."""
    catalog = mod_integrity.load_catalog(tree['idx'])
    rel = sorted(catalog)[0]
    shard = os.path.join(tree['idx'], rel)
    handle = mod_iqmt.checkout_shard(shard)     # leased, healthy
    _flip_byte(shard)
    with pytest.raises(DNError):
        mod_integrity.verify_shard(shard)       # quarantines + bumps
    mod_iqmt.checkin_shard(handle, ok=True)
    assert mod_iqmt.shard_cache_stats()['size'] == 0


def test_quarantined_catalog_tmp_swept(tmp_path):
    """A catalog tmp of a dead writer is quarantined by the sweep —
    the committed catalog is untouched."""
    idx = str(tmp_path / 'idx')
    os.makedirs(idx)
    mod_integrity.update_catalog(idx, add={'all': (3, 7)})
    tmp = os.path.join(
        idx, mod_journal.INTEGRITY_NAME + '.999999.tmp')
    with open(tmp, 'w') as f:
        f.write('{torn')
    mod_journal.sweep_index_tree(idx)
    assert not os.path.exists(tmp)
    assert mod_integrity.load_catalog(idx) == {'all': (3, 7)}


# -- the flip fault kind ----------------------------------------------------


def test_flip_fault_corrupts_committed_shard(tmp_path, monkeypatch):
    """`sink.rename:flip:1.0` lands a published shard whose bytes
    disagree with the catalog (the checksum rode the commit record
    BEFORE the flip) — exactly the post-publish rot verified reads
    catch; replays are deterministic."""
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=600)
    monkeypatch.setenv('DN_FAULTS', 'sink.rename:flip:1.0:3')
    mod_faults.reset()
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')        # publish succeeds silently
    monkeypatch.delenv('DN_FAULTS')
    mod_faults.reset()
    catalog = mod_integrity.load_catalog(idx)
    corrupt = [rel for rel, ent in catalog.items()
               if mod_integrity.file_crc(
                   os.path.join(idx, rel)) != ent]
    assert len(corrupt) == len(catalog)   # rate 1.0: every shard
    monkeypatch.setenv('DN_VERIFY', 'open')
    with pytest.raises(DNError) as ei:
        ds.query(_query(), 'day')
    assert getattr(ei.value, 'corrupt_shard', None) is not None


def test_flip_without_path_degrades_to_error(tmp_path, monkeypatch,
                                             tree):
    """flip at a site that hands no file path degrades to a clean
    injected error, mirroring torn semantics."""
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:flip:1.0')
    mod_faults.reset()
    with pytest.raises(DNError):
        tree['ds'].query(_query(), 'day')
    monkeypatch.delenv('DN_FAULTS')
    mod_faults.reset()


# -- scrub ------------------------------------------------------------------


def test_scrub_clean_tree_zero_diffs(tree):
    res = mod_integrity.scrub_tree(tree['idx'])
    assert res['corrupt'] == 0 and res['missing'] == 0
    assert res['verified'] == len(
        mod_integrity.load_catalog(tree['idx']))


def test_scrub_detects_quarantines_and_reports_missing(tree):
    catalog = mod_integrity.load_catalog(tree['idx'])
    rels = sorted(catalog)
    _flip_byte(os.path.join(tree['idx'], rels[0]))
    os.unlink(os.path.join(tree['idx'], rels[1]))
    # --check reports without acting
    res = mod_integrity.scrub_tree(tree['idx'], quarantine=False)
    assert res['corrupt_shards'] == [rels[0]]
    assert res['missing_shards'] == [rels[1]]
    assert os.path.exists(os.path.join(tree['idx'], rels[0]))
    # the real pass quarantines
    res = mod_integrity.scrub_tree(tree['idx'])
    assert res['corrupt_shards'] == [rels[0]]
    assert not os.path.exists(os.path.join(tree['idx'], rels[0]))
    qdir = os.path.join(tree['idx'], mod_journal.QUARANTINE_DIR)
    assert os.path.basename(rels[0]) in os.listdir(qdir)
    # forget-missing drops the entries the operator gave up on
    res = mod_integrity.scrub_tree(tree['idx'], forget_missing=True)
    assert sorted(res['missing_shards']) == sorted(rels[:2])
    left = mod_integrity.load_catalog(tree['idx'])
    assert rels[0] not in left and rels[1] not in left


def test_scrub_cli_and_quarantine_cli(tree, tmp_path, monkeypatch):
    """`dn scrub --tree` / `dn quarantine list|clean --older-than`
    end to end, including the age gate and rc contracts."""
    catalog = mod_integrity.load_catalog(tree['idx'])
    rel = sorted(catalog)[0]
    rc, out, err = run_cli(['scrub', '--tree', tree['idx']])
    assert rc == 0, err
    assert json.loads(out)[tree['idx']]['verified'] == len(catalog)
    _flip_byte(os.path.join(tree['idx'], rel))
    rc, out, err = run_cli(['scrub', '--tree', tree['idx']])
    assert rc == 1
    doc = json.loads(out)[tree['idx']]
    assert doc['corrupt_shards'] == [rel]
    rc, out, err = run_cli(['quarantine', 'list', '--tree',
                            tree['idx']])
    assert rc == 0
    assert os.path.basename(rel).encode() in out
    # too-young entries survive an age-gated clean...
    rc, out, err = run_cli(['quarantine', 'clean', '--tree',
                            tree['idx'], '--older-than', '1d'])
    assert rc == 0 and b'removed 0' in err
    # ...and an ungated clean removes them
    rc, out, err = run_cli(['quarantine', 'clean', '--tree',
                            tree['idx']])
    assert rc == 0 and b'removed 1' in err
    qdir = os.path.join(tree['idx'], mod_journal.QUARANTINE_DIR)
    assert os.listdir(qdir) == []


def test_serve_validate_prints_integrity_line(tmp_path, monkeypatch):
    monkeypatch.setenv('DN_VERIFY', 'open')
    monkeypatch.setenv('DN_SCRUB_INTERVAL_S', '45')
    rc, out, err = run_cli(['serve', '--socket',
                            str(tmp_path / 's.sock'), '--validate'])
    assert rc == 0, err
    assert b'integrity config ok: verify=open scrub_interval_s=45' \
        in out
    monkeypatch.setenv('DN_VERIFY', 'bogus')
    rc, out, err = run_cli(['serve', '--socket',
                            str(tmp_path / 's.sock'), '--validate'])
    assert rc == 1
    assert b'DN_VERIFY' in err


# -- cluster self-healing ---------------------------------------------------


@pytest.fixture
def healing_cluster(tmp_path, monkeypatch):
    """Three in-process members with PRIVATE byte-identical trees
    (members[].config), verify=open: the harness for detect ->
    failover -> background repair."""
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '1')
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    monkeypatch.setenv('DN_REMOTE_CONNECT_TIMEOUT_S', '2')
    monkeypatch.delenv('DN_VERIFY', raising=False)
    from dragnet_tpu.serve import topology as mod_topology
    root = tmp_path
    datafile = str(root / 'data.log')
    _make_data(datafile, n=1200)
    rc_path = str(root / 'dragnetrc.json')
    monkeypatch.setenv('DRAGNET_CONFIG', rc_path)
    idx = str(root / 'idx')
    rc, out, err = run_cli(['datasource-add', '--path', datafile,
                            '--index-path', idx, '--time-field',
                            'time', 'ds1'])
    assert rc == 0, err
    rc, out, err = run_cli(['metric-add', '-b', 'host', 'ds1', 'm1'])
    assert rc == 0, err
    rc, out, err = run_cli(['build', 'ds1'])
    assert rc == 0, err
    doc = json.load(open(rc_path))
    member_rc = {}
    for m in 'abc':
        shutil.copytree(idx, str(root / ('idx_' + m)))
        d2 = json.loads(json.dumps(doc))
        d2['datasources'][0]['backend_config']['indexPath'] = \
            str(root / ('idx_' + m))
        p = str(root / ('rc_%s.json' % m))
        with open(p, 'w') as f:
            json.dump(d2, f)
        member_rc[m] = p
    socks = {m: str(root / ('dn-%s.sock' % m)) for m in 'abc'}
    topo_path = str(root / 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump({
            'epoch': 1, 'assign': 'hash',
            'members': {m: {'endpoint': socks[m],
                            'config': member_rc[m]} for m in 'abc'},
            'partitions': [
                {'id': 0, 'replicas': ['a', 'b']},
                {'id': 1, 'replicas': ['b', 'c']},
                {'id': 2, 'replicas': ['c', 'a']},
            ]}, f)
    conf = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10}
    servers = {}
    for m in 'abc':
        topo = mod_topology.load_topology(topo_path, member=m)
        servers[m] = mod_server.DnServer(
            socket_path=socks[m], conf=dict(conf), cluster=topo,
            member=m).start()
    monkeypatch.setenv('DN_VERIFY', 'open')
    mod_integrity.reset_memo()
    try:
        yield {'servers': servers, 'socks': socks,
               'rc_path': rc_path, 'root': str(root)}
    finally:
        for srv in servers.values():
            srv.stop()


def _routed_query(cluster, via='a'):
    from dragnet_tpu.serve import client as mod_client
    req = {'op': 'query', 'ds': 'ds1', 'config': cluster['rc_path'],
           'queryconfig': {'breakdowns': [{'name': 'host',
                                           'field': 'host'}]},
           'interval': 'day', 'opts': {}}
    return mod_client.request_bytes(cluster['socks'][via], req,
                                    timeout_s=30)


def _partition1_shard(cluster, member):
    from dragnet_tpu.serve import scrub as mod_scrub
    idx = os.path.join(cluster['root'], 'idx_' + member)
    topo = cluster['servers']['a'].cluster
    catalog = mod_integrity.load_catalog(idx)
    for rel in sorted(catalog):
        if topo.partition_of(os.path.join(idx, rel),
                             mod_scrub.rel_timeformat(rel)) == 1:
            return idx, rel, catalog[rel]
    raise AssertionError('no partition-1 shard in %s' % idx)


def _wait_healed(path, expected, timeout_s=25.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            if mod_integrity.file_crc(path) == expected:
                return True
        except OSError:
            pass
        time.sleep(0.2)
    return False


def test_cluster_detect_failover_and_self_heal(healing_cluster):
    """The headline contract: a corrupt shard on member b (a
    partition the router does not replicate) -> b rejects retryably
    with the corrupt_shard header, the router fails over to c
    (routed bytes stay IDENTICAL), and b repairs itself from a
    committed co-replica in the background — byte-identity restored,
    verified against the donor's catalog entry."""
    from dragnet_tpu.serve import client as mod_client
    rc, hdr, gold, err = _routed_query(healing_cluster)
    assert rc == 0, err
    idx_b, rel, expected = _partition1_shard(healing_cluster, 'b')
    shard = os.path.join(idx_b, rel)
    _flip_byte(shard)
    mod_iqmt.shard_cache_clear()
    rc, hdr, out, err = _routed_query(healing_cluster)
    assert rc == 0, err
    assert out == gold
    assert _wait_healed(shard, expected), 'repair never landed'
    # catalog entry survived and the repaired copy verifies
    assert mod_integrity.load_catalog(idx_b)[rel] == expected
    # the repairer counts a completion AFTER the rename that
    # _wait_healed saw, so wait for the counter, not for the file
    deadline = time.time() + 25.0
    while True:
        doc_b = mod_client.stats(healing_cluster['socks']['b'],
                                 timeout_s=10)
        rep = doc_b['integrity']['repair']
        if rep['completed'] >= 1 or time.time() >= deadline:
            break
        time.sleep(0.05)
    assert rep['completed'] >= 1 and rep['scheduled'] >= 1
    assert doc_b['integrity']['corrupt_shards'] >= 1
    assert doc_b['recovery']['quarantine_files'] >= 1
    doc_a = mod_client.stats(healing_cluster['socks']['a'],
                             timeout_s=10)
    assert doc_a['cluster']['counters']['corrupt_failovers'] >= 1
    # steady state: routed queries stay byte-identical post-repair
    rc, hdr, out, err = _routed_query(healing_cluster)
    assert rc == 0 and out == gold


def test_cluster_local_detect_self_heals(healing_cluster):
    """The router's OWN partial hitting a corrupt shard schedules
    repair too (the error propagates to the router, not through the
    request error path — regression for the detect-time hook)."""
    rc, hdr, gold, err = _routed_query(healing_cluster)
    assert rc == 0, err
    # a replicates partitions 0 and 2 — the router ranks ITSELF
    # first for those, so their partials execute in-process
    idx_a = os.path.join(healing_cluster['root'], 'idx_a')
    topo = healing_cluster['servers']['a'].cluster
    from dragnet_tpu.serve import scrub as mod_scrub
    catalog = mod_integrity.load_catalog(idx_a)
    mine = set(topo.partitions_of('a'))
    rel = next(r for r in sorted(catalog)
               if topo.partition_of(os.path.join(idx_a, r),
                                    mod_scrub.rel_timeformat(r))
               in mine)
    shard = os.path.join(idx_a, rel)
    _flip_byte(shard)
    mod_iqmt.shard_cache_clear()
    rc, hdr, out, err = _routed_query(healing_cluster)
    assert rc == 0 and out == gold
    assert _wait_healed(shard, catalog[rel]), 'repair never landed'


def test_remote_scrub_clean_cluster_reports_zero_diffs(
        healing_cluster):
    """`dn scrub --remote` against a clean member: zero corrupt, zero
    missing, deterministic anti-entropy no-op (nothing pulled,
    nothing diverged)."""
    rc, out, err = run_cli(['scrub', '--remote',
                            healing_cluster['socks']['c']])
    assert rc == 0, err
    doc = json.loads(out)
    t = doc['trees']['ds1']
    assert t['corrupt'] == 0 and t['missing'] == 0
    assert t['verified'] == len(mod_integrity.load_catalog(
        os.path.join(healing_cluster['root'], 'idx_c')))
    ae = doc['anti_entropy']['ds1']
    assert ae['pulled'] == 0 and ae['diverged'] == 0
    assert ae['checked'] > 0


def test_anti_entropy_pulls_lost_shard(healing_cluster):
    """A member that lost a shard AND its catalog entry (total local
    amnesia) gets it back from a co-replica's manifest via the scrub
    op — the anti-entropy leg."""
    idx_b, rel, expected = _partition1_shard(healing_cluster, 'b')
    shard = os.path.join(idx_b, rel)
    os.unlink(shard)
    mod_integrity.update_catalog(idx_b, remove=[rel])
    mod_iqmt.shard_cache_clear()
    mod_integrity.reset_memo()
    rc, out, err = run_cli(['scrub', '--remote',
                            healing_cluster['socks']['b'],
                            '--repair'])
    doc = json.loads(out)
    assert doc['anti_entropy']['ds1']['pulled'] >= 1
    assert mod_integrity.file_crc(shard) == expected
    assert mod_integrity.load_catalog(idx_b)[rel] == expected
