"""The cluster cell's own tests (CPU; `python -m pytest benchmarks/tests -q`):
`muskie-365d-index-cluster4.query-windows`, its driver
`drivers/serve_cluster.py` and its five metric files (PR 44).

* each metric file reads a number from the recorded merged scrape pair
  of the change, and nothing (and raises nothing) from the parent's,
  which writes none of the new series;
* the arithmetic of the two that are not a plain ratio;
* the driver's pieces without a child: the topology it writes, the
  dealing of the client threads, the scrapes added, the least growth;
* one 20,000-record rehearsal of the cell through four XLA:CPU
  members, and one with a partial's weight altered where the member
  produces it: `correct` comes out false, by the comparison.

The scrapes are `data/cluster4_scrapes.json`: 20,000-record CPU
rehearsals, so their numbers stand for nothing but their names.
"""

import json
import os
import sys
import threading
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from loader import load_module                            # noqa: E402
from obs import prom                                      # noqa: E402
import test_benchmark as tb                               # noqa: E402
from test_benchmark import throwaway                      # noqa: E402,F401

run = load_module('.', 'run')
cluster = load_module('drivers', 'serve_cluster')

CELL = 'muskie-365d-index-cluster4.query-windows'
NEW_METRICS = ('router_scatter_ms.query', 'router_merge_ms.query',
               'partial_export_ms.query', 'partial_items_per_query.query',
               'partial_device_share.query')


def _scrapes():
    with open(os.path.join(HERE, 'data', 'cluster4_scrapes.json')) as f:
        return json.load(f)


def _reading(before, after, done):
    outcome = types.SimpleNamespace(
        ok=True, err=None,
        req=types.SimpleNamespace(template={'op': 'query', 'name': 'm1'}))
    ctx = types.SimpleNamespace(
        config={'corpus': {'records': 20000, 'days': 365}},
        workload={'name': CELL}, say=lambda msg: None)
    res = {'prom_before': before, 'prom_after': after,
           'stats_before': {}, 'stats_after': {}, 'window_stderr': '',
           'outcomes': [outcome] * done, 'window_s': 2.0,
           'device': {'kind': 'cpu', 'platform': 'cpu', 'count': 4}}
    return run.Reading(ctx, res, None)


def _recorded(side):
    rec = _scrapes()[side]
    return _reading(rec['before'], rec['after'], rec['done']['query'])


# -- the five metric files --------------------------------------------------

@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_a_number(metric):
    mod = load_module('metrics', metric)
    value = mod.read(_recorded('change'))
    assert isinstance(value, float)
    assert 0.0 < value <= (100.0 if mod.META['unit'] == '%'
                           else float('inf'))
    assert mod.META['moves'] == 'query_completed_per_s'
    assert set(mod.META) == {'layer', 'source', 'unit', 'better', 'moves'}


@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_nothing_from_the_parents_scrape(metric):
    """The program before PR 44: the router's stages are spans and no
    leaves, and it counts neither the items nor the lanes."""
    mod = load_module('metrics', metric)
    assert mod.read(_recorded('parent')) is None


def test_the_recorded_partials_took_the_device_lane():
    """Every partial of the recorded window that had a shard to fold
    was summed by the device fold (XLA:CPU there); the partials of
    slices with no shard in the window are `empty` and not counted."""
    r = _recorded('change')
    assert r.delta('cluster_partials_total', lane='empty') > 0
    assert load_module('metrics', 'partial_device_share.query').read(r) \
        == 100.0


@pytest.mark.parametrize('lanes,want', [
    ({'device': 30, 'stacked': 10, 'shard': 10, 'empty': 50}, 60.0),
    ({'shard': 7}, 0.0), ({'device': 4}, 100.0), ({'empty': 3}, None),
    ({}, None)])
def test_partial_device_share_arithmetic(lanes, want):
    text = ''.join('dn_cluster_partials_total{lane="%s"} %d\n' % kv
                   for kv in lanes.items())
    got = load_module('metrics', 'partial_device_share.query').read(
        _reading('', text, 5))
    assert got == want


def test_per_partial_and_per_query_denominators():
    """The export is a partial's (its stage's own count), the items and
    the router's two leaves are a finished query's."""
    after = ('dn_stage_ms_sum{stage="index_query_stack.export"} 12.0\n'
             'dn_stage_ms_count{stage="index_query_stack.export"} 24.0\n'
             'dn_stage_ms_sum{stage="router.scatter"} 300.0\n'
             'dn_stage_ms_count{stage="router.scatter"} 7.0\n'
             'dn_stage_ms_sum{stage="router.merge"} 9.0\n'
             'dn_router_partial_items_total 4800.0\n')
    r = _reading('', after, 6)
    read = lambda name: load_module('metrics', name).read(r)
    assert read('partial_export_ms.query') == 0.5
    assert read('router_scatter_ms.query') == 50.0
    assert read('router_merge_ms.query') == 1.5
    assert read('partial_items_per_query.query') == 800.0


# -- the driver's pieces ----------------------------------------------------

def test_topology_is_the_configurations():
    """Four members on unix sockets of the run directory, four hash
    partitions of two replicas each in preference order, one shared
    tree, and a document the program's own loader accepts."""
    sys.path.insert(0, ROOT)
    from dragnet_tpu.serve import topology as mod_topology
    cfg = tb._load('configs', 'muskie-365d-index-cluster4')
    doc = cluster.topology_doc(cfg['cluster'])
    assert mod_topology.validate_doc(json.loads(json.dumps(doc))) is None
    assert doc['assign'] == 'hash' and doc['epoch'] == 1
    assert sorted(doc['members']) == ['a', 'b', 'c', 'd']
    assert all(set(m) == {'endpoint'} for m in doc['members'].values())
    assert [p['replicas'] for p in doc['partitions']] == \
        [['a', 'b'], ['b', 'c'], ['c', 'd'], ['d', 'a']]
    topo = mod_topology.Topology(doc)
    assert all(len(topo.partitions_of(m)) == 2 for m in 'abcd')


def test_the_cells_traffic_is_the_one_chip_cells():
    """Letter for letter, but for the names, the driver and the lists
    of what is reported."""
    ours, theirs = (tb._load('workloads', c) for c in
                    (CELL, 'muskie-365d-index.query-windows'))
    own = {'name', 'config', 'driver', 'why', 'per_layer', 'control'}
    assert {k: v for k, v in ours.items() if k not in own} == \
        {k: v for k, v in theirs.items() if k not in own}
    assert ours['driver'] == 'serve_cluster' and ours['clients'] == 8
    cfg, base = (tb._load('configs', c) for c in
                 ('muskie-365d-index-cluster4', 'muskie-365d-index'))
    for k in ('corpus', 'metrics', 'datasource', 'index_interval',
              'environment', 'setup_build_environment', 'reduced'):
        assert cfg[k] == base[k], k
    assert cfg['guarantees'][:len(base['guarantees'])] == base['guarantees']


def test_chip_env_shows_one_chip_each():
    envs = [cluster.chip_env(k) for k in range(4)]
    assert [e['TPU_VISIBLE_CHIPS'] for e in envs] == ['0', '1', '2', '3']
    assert len({e['TPU_PROCESS_PORT'] for e in envs}) == 4
    assert all(e['TPU_PROCESS_BOUNDS'] == '1,1,1' and
               e['TPU_CHIPS_PER_PROCESS_BOUNDS'] == '1,1,1' for e in envs)


def test_clients_are_dealt_round_robin():
    """Eight client threads over four members: two each; any other
    thread talks to the member pinned last."""
    members = [types.SimpleNamespace(sock='dn-%s.sock' % m) for m in 'abcd']
    dealer = cluster.Dealer(members)
    seen = {}

    def client():
        seen[threading.current_thread().name] = dealer.sock
    threads = [threading.Thread(target=client, name='bench-client-%d' % k)
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [seen['bench-client-%d' % k] for k in range(8)] == \
        ['dn-%s.sock' % m for m in 'abcdabcd']
    assert dealer.sock == 'dn-a.sock'
    dealer.pin(members[2])
    assert dealer.sock == 'dn-c.sock'


def test_scrapes_are_added_sample_by_sample():
    a = ('# HELP x\ndn_stage_ms_sum{stage="router.merge"} 1.5\n'
         'dn_stage_ms_bucket{le="1",stage="router.merge"} 2\n'
         'dn_xla_compiles_total 0\n')
    b = ('dn_stage_ms_sum{stage="router.merge"} 2.25\n'
         'dn_stage_ms_bucket{le="1",stage="router.merge"} 3\n'
         'dn_cluster_partials_total{lane="device"} 7\n')
    got = prom.parse(cluster.merge_prom([a, b]))
    assert prom.value(got, 'stage_ms_sum', {'stage': 'router.merge'}) == 3.75
    assert prom.value(got, 'stage_ms_bucket',
                      {'stage': 'router.merge', 'le': '1'}) == 5.0
    assert prom.value(got, 'xla_compiles_total') == 0.0
    assert prom.value(got, 'cluster_partials_total',
                      {'lane': 'device'}) == 7.0


def test_engagement_is_the_least_of_the_members():
    """`index device sums` must grow on every member: the merged growth
    is the smallest member's, so one member answering from its host
    fails run.py's engagement check."""
    name = 'index device sums'
    before = cluster.merge_stats({
        'a': {'counters': {name: 10, 'other': 1}},
        'b': {'counters': {name: 20}}, 'c': {'counters': {}},
        'd': {'counters': {name: 5}}})
    after = cluster.merge_stats({
        'a': {'counters': {name: 110, 'other': 4}},
        'b': {'counters': {name: 90}}, 'c': {'counters': {name: 0}},
        'd': {'counters': {name: 65}}})
    assert before['counters'] == {name: 35, 'other': 1}
    assert cluster.least_growth(before, after, name) == [100, 70, 0, 60]
    assert after['counters'][name] - before['counters'][name] == 0
    assert after['counters']['other'] - before['counters']['other'] == 3


def test_device_count_is_the_members_on_one_chip_each():
    members = [types.SimpleNamespace(name=m, chip=k)
               for k, m in enumerate('abcd')]
    tpu = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1,
           'memory_peak_bytes': 100}
    docs = {m: dict(tpu) for m in 'abcd'}
    docs['c']['memory_peak_bytes'] = 300
    got = cluster.merge_devices(members, docs)
    assert (got['platform'], got['kind'], got['count'],
            got['memory_peak_bytes']) == ('tpu', 'TPU v5 lite', 4, 300)
    assert got['members']['d']['chip'] == 3
    docs['b']['count'] = 4           # a member that sees the whole host
    assert cluster.merge_devices(members, docs)['count'] == 3
    cpu = {m: {'platform': 'cpu', 'kind': 'cpu', 'count': 4,
               'memory_peak_bytes': 0} for m in 'abcd'}
    assert cluster.merge_devices(members, cpu)['count'] == 4


# -- rehearsals -------------------------------------------------------------

# one partial's first weight one too high, where the member produces it
BROKEN_PARTIAL = '''"""The normal launcher with a member's partial altered underneath."""
import sys
sys.path.insert(0, %(root)r)
from dragnet_tpu.serve import router
_real = router.partial_query


def _broken(ds, query, interval, topology, partition_ids):
    shards = _real(ds, query, interval, topology, partition_ids)
    for rel, items in shards:
        if items:
            items[0][1] += 1
            break
    return shards


router.partial_query = _broken
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def _rehearsal(name, trace=0):
    rc, lines = tb._rehearse(name, trace=trace)
    assert rc != 0 and lines[-1].startswith('rehearsal '), lines[-5:]
    return json.loads(lines[-1][len('rehearsal '):]), lines


def test_rehearsal_through_four_members(throwaway):
    """20,000 records, four XLA:CPU members: every answer equals the
    reference, the device lane engaged on every member (the line names
    the four growths), every listed metric a CPU can read is read."""
    name, cfg = tb._small_copy(throwaway, CELL)
    doc, lines = _rehearsal(name, trace=1)
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values()), lines
    assert doc['failed'] == 0 and doc['attempted'] > 0
    assert doc['device']['count'] == 4
    assert sorted(doc['device']['members']) == ['a', 'b', 'c', 'd']
    grew = [ln for ln in lines
            if ln.startswith('engagement: the members\' growth')]
    assert len(grew) == 1
    assert all(int(g) > 0 for g in grew[0].split(': ')[2].split()), grew
    assert not [ln for ln in lines if 'did not engage' in ln]
    wl = tb._load('workloads', CELL)
    device_trace = {'device_idle_share.query'}
    assert set(wl['per_layer']) - device_trace <= set(doc['metrics'])
    assert doc['metrics']['partial_device_share.query']['value'] == 100.0
    assert doc['metrics']['partial_items_per_query.query']['value'] > 0


def test_altered_partial_is_not_correct(throwaway):
    launcher = throwaway('tests', 't_broken_partial.py', BROKEN_PARTIAL % {
        'root': ROOT,
        'launcher': os.path.join(BENCH, 'drivers', 'launch_serve.py')})
    name, _ = tb._small_copy(throwaway, CELL, launcher=launcher)
    doc, lines = _rehearsal(name)
    assert doc['correct'] is False
    assert any('count_difference' in ln and 'over its limit' in ln
               for ln in lines), lines
    assert doc['numbers_compared']['window.count_difference']['value'] > 0

