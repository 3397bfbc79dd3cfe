"""Ask the TPU v5e compiler, without the chip.

The kernels and jitted programs of the scan / build / query device path
are compiled here for a DESCRIBED v5e (no device attached), at the real
batch widths and with x64 on as the program has it.  What Mosaic or the
TPU backend would refuse on the chip — a 64-bit vector layout in the
one-hot kernel, a misaligned tile, a program over the memory budget, a
shard_map that cannot be partitioned — is refused here, at no chip
time.  Nothing runs: results and times come only from a chip run
(chip_smoke.py).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and xdist workers import
every test file.  All of these tests stay in this one file for the
same reason, and none of them starts a child process.

Not covered: DeviceScan asks `jax.default_backend()` for buffer
donation (device_scan._donate_kw) and sees the CPU here, so the
programs compile WITHOUT `donate_argnums`; and DeviceScanStack's
jit is compiled for a stack of one, a multi-metric build's only
through its member folds.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench                                        # noqa: E402
from dragnet_tpu import device_index                # noqa: E402
from dragnet_tpu.device_scan import (               # noqa: E402
    DeviceScan, DeviceScanStack)
from dragnet_tpu import engine                      # noqa: E402
from dragnet_tpu import native as mod_native        # noqa: E402
from dragnet_tpu import query as mod_query          # noqa: E402
from dragnet_tpu.ops import get_jax                 # noqa: E402
from dragnet_tpu.ops import byteparse_kernels       # noqa: E402
from dragnet_tpu.ops import kernels                 # noqa: E402
from dragnet_tpu.ops import pallas_kernels as pk    # noqa: E402
from dragnet_tpu.parallel import mesh as mod_mesh   # noqa: E402
from dragnet_tpu.vpipe import Pipeline              # noqa: E402
from helpers.one_batch import one_batch_parser      # noqa: E402

BATCH = engine.BATCH_SIZE
# forces the device-resident sparse sort-merge program at any corpus
# size (chip_smoke.py's scan-sparse phase)
SPARSE_QUERY = {'breakdowns': [{'name': 'req.url'}, {'name': 'latency'},
                               {'name': 'dataLatency'}],
                'filter': {'eq': ['req.method', 'GET']}}


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    jax, _ = get_jax()
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform='tpu',
                                         topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def mesh4(topo):
    from jax.sharding import Mesh
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ('d',))


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    """One full batch of generated muskie-style records."""
    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    path = str(tmp_path_factory.mktemp('tpu_compile') / 'batch.log')
    bench.gen_to_file(BATCH, path)
    return path


def _sds(shape, dtype, sharding):
    jax, _ = get_jax()
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _like(tree, sharding):
    """ShapeDtypeStructs placed on `sharding` for every array leaf."""
    jax, _ = get_jax()
    return jax.tree_util.tree_map(
        lambda x: _sds(np.shape(x), x.dtype, sharding), tree)


def _compile(fn, *args):
    jax, _ = get_jax()
    if not hasattr(fn, 'lower'):
        fn = jax.jit(fn)
    return fn.lower(*args).compile()


# -- aggregation kernels ------------------------------------------------------

@pytest.mark.parametrize('radices', [(16, 32), (64, 64)])
def test_onehot_kernel_compiles_with_mosaic(one_chip, radices):
    """The Pallas one-hot kernel at the engine's batch size, under x64,
    NOT in interpret mode; (64, 64) is MAX_PALLAS_SEGMENTS."""
    assert radices[0] * radices[1] <= pk.MAX_PALLAS_SEGMENTS

    def agg(codes, weights, alive):
        return pk.onehot_dense(radices, BATCH, codes, weights, alive,
                               interpret=False)
    compiled = _compile(agg,
                        _sds((2, BATCH), np.int32, one_chip),
                        _sds((BATCH,), np.float32, one_chip),
                        _sds((BATCH,), np.bool_, one_chip))
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.parametrize('radices', [(16, 32), (64, 64)])
def test_segment_sum_aggregate_compiles(one_chip, radices):
    agg = kernels.make_aggregate(radices, BATCH, True)
    _compile(agg,
             _sds((2, BATCH), np.int32, one_chip),
             _sds((BATCH,), np.int32, one_chip),
             _sds((BATCH,), np.bool_, one_chip))


# -- the DeviceScan programs --------------------------------------------------

def _staged_program(query_conf, datafile, scan_cls=None, time_field=None):
    """(jitted program, example inputs, accumulator shapes, use_pallas)
    of the program a lone DeviceScan, a stack of one, builds for one
    real batch of `datafile`: staged on the CPU backend exactly as a
    scan stages it, but not run.  The program takes the stack's
    accumulators: (accumulator,)."""
    jax, _ = get_jax()
    if scan_cls is None:
        scan_cls = DeviceScan
    scan = scan_cls(mod_query.query_load(dict(query_conf)), time_field,
                    Pipeline())
    parser = one_batch_parser(datafile, scan, BATCH)
    n = parser.batch_size()
    assert n == BATCH
    assert scan._probe_backend()
    inputs = {}
    staged = scan._stage_device(engine.NativeColumns(parser),
                                np.ones(n, dtype=np.float64), None,
                                inputs)
    assert staged is not None, 'batch was not eligible for the device'
    progs, use_pallas = scan._staged_programs(staged)
    # the one jitted program of a stack of one; the accumulator's
    # shapes stand in for it (none can be made on a described mesh)
    acc = scan._acc = jax.eval_shape(progs.acc_init)
    run = DeviceScanStack([scan])._stacked_program([staged], inputs)
    scan._acc = None
    return run, inputs, acc, use_pallas


@pytest.mark.parametrize('name,query_conf,sparse', [
    ('dense', bench.QUERY, False),
    ('wide-dense', bench.HC_QUERY, False),
    ('sparse', SPARSE_QUERY, True),
])
def test_device_scan_program_compiles(one_chip, corpus, name,
                                      query_conf, sparse):
    run, inputs, acc, use_pallas = _staged_program(query_conf, corpus)
    assert not use_pallas
    assert (len(acc) == 5) == sparse     # the sparse set's five leaves
    _compile(run, _like(inputs, one_chip), (_like(acc, one_chip),))


def test_bounded_scan_program_takes_its_bounds_as_arguments(one_chip,
                                                            corpus):
    """A scan with time bounds (a build of one day's window): the
    bounds are two int32 scalars among the program's arguments, the
    program of another day is the same cached one, and the v5e's
    compiler takes it."""
    hour = 3600000
    # the corpus's own window: three hours of one evening
    start = int(bench._mktestdata().MINDATE.timestamp() * 1000)
    assert start % hour == 0
    runs = []
    by_hour = {'name': 'timestamp', 'field': 'time', 'date': '',
               'aggr': 'lquantize', 'step': 3600}
    for after in (start, start + 2 * hour):
        conf = dict(bench.QUERY, timeAfter=after, timeBefore=after + hour,
                    breakdowns=[by_hour] + bench.QUERY['breakdowns'])
        run, inputs, acc, _ = _staged_program(conf, corpus,
                                              time_field='time')
        assert inputs['tb_lo'] == after // 1000
        assert inputs['tb_hi'] == (after + hour) // 1000
        assert inputs['tb_lo'].dtype == np.int32
        # the hour column's window starts at the bounds' hour, as an
        # argument too
        assert inputs['lo_timestamp'] == after // hour
        runs.append(run)
    assert runs[0] is runs[1]
    _compile(run, _like(inputs, one_chip), (_like(acc, one_chip),))


def test_device_scan_pallas_program_compiles(one_chip, corpus,
                                             monkeypatch):
    """PALLAS_QUERY's program with the Mosaic kernel inside the fold.
    The routing gate and the interpret switch both ask for the live
    backend, which is the CPU here: steer them as the chip would."""
    monkeypatch.setenv('DN_PALLAS', 'force')
    monkeypatch.setattr(pk, 'needs_interpret', lambda: False)
    run, inputs, acc, use_pallas = _staged_program(bench.PALLAS_QUERY,
                                                   corpus)
    assert use_pallas
    compiled = _compile(run, _like(inputs, one_chip),
                        (_like(acc, one_chip),))
    assert 'tpu_custom_call' in compiled.as_text()


# -- index query and parse lanes ---------------------------------------------

@pytest.mark.parametrize('rows,segments', [
    (rows, device_index.SEGMENT_FLOOR)
    for rows in device_index.ladder(device_index.ROW_PREWARM_TOP)] + [
    (device_index.ladder()[-1],
     device_index.pad_segments(engine.MAX_DENSE_SEGMENTS))])
def test_index_fold_compiles(one_chip, rows, segments):
    """The packed fold's one program at every rung residency.prewarm
    compiles (up to 2^20 rows), and at the coarse ladder's largest
    shape: 2^18 rows into the widest accumulator the lane admits
    (MAX_DENSE_SEGMENTS)."""
    prog = device_index.sums_program(rows, segments)
    compiled = _compile(prog, _sds((2, rows), np.int64, one_chip))
    assert 's64[%d]' % segments in compiled.as_text()


def test_byteparse_parity_compiles(one_chip):
    fn = byteparse_kernels._jax_fn()
    _compile(fn, _sds((byteparse_kernels.PAD_QUANTUM,), np.uint8,
                      one_chip))


# -- the mesh path ------------------------------------------------------------

def test_mesh_device_scan_program_compiles(mesh4, corpus, monkeypatch):
    """The cluster backend's whole-pipeline program (`dn scan` on a
    `--backend=cluster` datasource, chip_smoke.py --chips 4): the
    DeviceScan body under shard_map over the four chips, dense weights
    and counters merged by psum, first occurrences by pmin."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dragnet_tpu.parallel import cluster
    monkeypatch.setattr(cluster.MeshDeviceScan, '_mesh_cache',
                        (mesh4, 'd'))
    run, inputs, acc, use_pallas = _staged_program(
        bench.QUERY, corpus, scan_cls=cluster.MeshDeviceScan)
    assert not use_pallas
    replicated = NamedSharding(mesh4, P())
    compiled = _compile(run, _like(inputs, replicated),
                        (_like(acc, replicated),))
    text = compiled.as_text()
    assert 'all-reduce' in text


COLLECTIVES = ('all-reduce', 'all-gather', 'all-to-all',
               'collective-permute', 'reduce-scatter')


def _sparse_sets(mesh, ndev, cap, ncnt=5):
    """Shapes of a mesh's sparse accumulator: a set a chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharded = NamedSharding(mesh, P('d'))
    return tuple(_sds((ndev, n), np.int64, sharded)
                 for n in (cap, cap, cap, ncnt, 2))


def test_mesh_sparse_fold_has_no_collective(corpus, monkeypatch):
    """The cluster backend's high-cardinality fold, as the virtual
    8-device CPU mesh compiles it: the sparse fold under shard_map, a
    set a chip, with NO collective in the partitioned program (nothing
    crosses the chips per batch; the sets meet at the flush)."""
    from dragnet_tpu import device_scan
    from dragnet_tpu.parallel import cluster
    monkeypatch.setattr(device_scan, 'SPARSE_CAP0', 1 << 14)
    monkeypatch.setattr(cluster.MeshDeviceScan, '_mesh_cache', None)
    run, inputs, acc, use_pallas = _staged_program(
        SPARSE_QUERY, corpus, scan_cls=cluster.MeshDeviceScan)
    assert not use_pallas
    assert [x.shape for x in acc[:3]] == [(8, 1 << 14)] * 3
    text = run.lower(inputs, (acc,)).compile().as_text()
    assert 'sort' in text
    for collective in COLLECTIVES:
        assert collective not in text, collective


def test_mesh_sparse_merge_compiles(mesh4):
    """The flush's merge of the chips' sets (parallel/mesh.py
    `sparse_merge_program`) for the four chips of a v5e 2x2, at a
    small size: its all-gather is there, every all-reduce in it is one
    the chip can lower (a 64-bit pmax is not: PR 35), and the merged
    set is replicated."""
    jax, _ = get_jax()
    acc = _sparse_sets(mesh4, 4, 1 << 14)
    merge = mod_mesh.sparse_merge_program(mesh4, 'd', 1 << 10, 1 << 12)
    assert 'all-gather' in _compile(merge, acc).as_text()
    merged = jax.eval_shape(merge, acc)
    assert [x.shape for x in merged] == [(1 << 12,)] * 3 + [(5,), (2,)]


@pytest.mark.slow
def test_mesh_sparse_programs_compile_at_the_cell_size(mesh4, corpus,
                                                       monkeypatch):
    """The benchmark cell's shapes (muskie-30d-highcard-mesh4): the
    fold into a set of 2^20 slots a chip, without a collective, and
    the merge of 2^17 slots a chip into a set of 2^19.  215 s of
    compilation here, so not among the quick tests."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dragnet_tpu.parallel import cluster
    monkeypatch.setattr(cluster.MeshDeviceScan, '_mesh_cache',
                        (mesh4, 'd'))
    run, inputs, acc, use_pallas = _staged_program(
        SPARSE_QUERY, corpus, scan_cls=cluster.MeshDeviceScan)
    assert not use_pallas and acc[0].shape == (4, 1 << 20)
    sets = _sparse_sets(mesh4, 4, 1 << 20, acc[3].shape[1])
    text = _compile(run, _like(inputs, NamedSharding(mesh4, P())),
                    (sets,)).as_text()
    for collective in COLLECTIVES:
        assert collective not in text, collective
    merge = mod_mesh.sparse_merge_program(mesh4, 'd', 1 << 17, 1 << 19)
    assert 'all-gather' in _compile(merge, sets).as_text()


@pytest.mark.parametrize('scatter,use_pallas,collective', [
    (False, False, ('all-reduce',)),
    # at this accumulator size the v5e compiler lowers psum_scatter
    # to an all-reduce plus a per-device dynamic-slice
    (True, False, ('reduce-scatter', 'all-reduce')),
    (False, True, ('all-reduce',)),
])
def test_sharded_aggregate_compiles_on_mesh(mesh4, scatter, use_pallas,
                                            collective):
    """parallel/mesh.py's sharded aggregate over the four chips of a
    v5e 2x2: the merge must be a collective, not a gather to one
    device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    radices = (16, 32)
    per_device = BATCH // 4
    step = mod_mesh.sharded_step(mesh4, radices, per_device, scatter,
                                 True, use_pallas, interpret=False)
    wdtype = np.float32 if use_pallas else np.int32
    compiled = _compile(
        step,
        _sds((2, BATCH), np.int32, NamedSharding(mesh4, P(None, 'd'))),
        _sds((BATCH,), wdtype, NamedSharding(mesh4, P('d'))),
        _sds((BATCH,), np.bool_, NamedSharding(mesh4, P('d'))))
    text = compiled.as_text()
    assert any(op in text for op in collective)
    assert ('tpu_custom_call' in text) == use_pallas
