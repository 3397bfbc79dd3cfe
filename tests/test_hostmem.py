"""The allocator policy (dragnet_tpu/hostmem.py): set once by the `dn`
entry point and by nothing else, skipped where the C library or the
user's environment says so, and visible at a metrics scrape.

The tests swap `mallopt` for a recorder (this process keeps the
allocator it has); the one that counts page faults does so in child
processes.
"""

import ctypes
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import cli, hostmem                       # noqa: E402
from dragnet_tpu.obs import export as obs_export           # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402

MALLOC_ENV = hostmem.USER_ENV + ('GLIBC_TUNABLES',)


@pytest.fixture
def unasked(monkeypatch):
    """A process in which nothing has asked for the policy yet and the
    environment says nothing about malloc."""
    monkeypatch.setattr(hostmem, '_state', None)
    for name in MALLOC_ENV:
        monkeypatch.delenv(name, raising=False)


@pytest.fixture
def mallopt_calls(unasked, monkeypatch):
    """`mallopt` swapped for one that records its calls and takes every
    value."""
    calls = []

    def fake(param, value):
        calls.append((param, value))
        return 1

    monkeypatch.setattr(hostmem, '_mallopt', lambda: fake)
    return calls


def held_lines():
    """The `allocator_policy_held` lines of a scrape."""
    text = obs_export.prometheus_text(obs_metrics.Registry(), counters={})
    return [ln for ln in text.splitlines()
            if ln.startswith('dn_allocator_policy_held')]


def test_applied_once_and_a_second_call_is_a_noop(mallopt_calls):
    assert hostmem.hold_allocator() == (True, 'applied')
    assert mallopt_calls == list(hostmem.POLICY)
    assert hostmem.hold_allocator() == (True, 'applied')
    assert len(mallopt_calls) == len(hostmem.POLICY)
    assert held_lines() == ['dn_allocator_policy_held{reason="applied"} 1']


def test_the_threshold_covers_the_read_chunk():
    # datasource_file._stream_native reads at most 1 << 24 bytes at a
    # time; glibc takes no threshold over 32 MiB on 64 bits
    values = dict(hostmem.POLICY)
    assert (1 << 24) < values[hostmem.M_MMAP_THRESHOLD] <= (1 << 25)


def test_no_mallopt_symbol_is_a_noop(unasked, monkeypatch):
    class NoSuchSymbol(object):
        def __getattr__(self, name):
            raise AttributeError(name)

    monkeypatch.setattr(ctypes, 'CDLL', lambda name: NoSuchSymbol())
    assert hostmem.hold_allocator() == (False, 'no_mallopt')
    assert held_lines() == [
        'dn_allocator_policy_held{reason="no_mallopt"} 0']


@pytest.mark.parametrize('name,value', [
    ('MALLOC_MMAP_THRESHOLD_', '1048576'),
    ('MALLOC_TRIM_THRESHOLD_', '268435456'),
    ('MALLOC_TOP_PAD_', '65536'),
    ('MALLOC_ARENA_MAX', '2'),
    ('GLIBC_TUNABLES', 'glibc.pthread.rseq=0:glibc.malloc.arena_max=4'),
])
def test_the_users_environment_wins(mallopt_calls, monkeypatch, name,
                                    value):
    monkeypatch.setenv(name, value)
    assert hostmem.hold_allocator() == (False, 'user_env')
    assert mallopt_calls == []
    assert held_lines() == ['dn_allocator_policy_held{reason="user_env"} 0']


def test_tunables_of_another_subsystem_do_not_count(mallopt_calls,
                                                    monkeypatch):
    monkeypatch.setenv('GLIBC_TUNABLES', 'glibc.pthread.rseq=0')
    assert hostmem.hold_allocator() == (True, 'applied')


def test_a_refused_value_reads_zero(mallopt_calls, monkeypatch):
    monkeypatch.setattr(hostmem, '_mallopt', lambda: lambda p, v: 0)
    assert hostmem.hold_allocator() == (False, 'refused')
    assert held_lines() == ['dn_allocator_policy_held{reason="refused"} 0']


def test_the_entry_point_calls_it(mallopt_calls):
    # what bin/dn.py runs; no command: usage, after the policy
    assert hostmem.state() is None
    assert held_lines() == []
    assert cli.main([]) == 2
    assert hostmem.state() == (True, 'applied')
    assert len(mallopt_calls) == len(hostmem.POLICY)


@pytest.mark.parametrize('module', ['dragnet_tpu', 'dragnet_tpu.native',
                                    'dragnet_tpu.serve.server'])
def test_a_bare_import_does_not_call_it(module):
    env = {k: v for k, v in os.environ.items() if k not in MALLOC_ENV}
    out = subprocess.run(
        [sys.executable, '-c',
         'import sys; sys.path.insert(0, %r); import %s; '
         'from dragnet_tpu import hostmem; print(hostmem.state())'
         % (REPO_ROOT, module)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=120)
    assert out.returncode == 0, out.stderr.decode()
    assert out.stdout.decode().strip() == 'None'


# A scan's allocation pattern with its lifetimes fixed: a reader thread
# (its own arena, as dn-read-ahead's) reads 16 MiB chunks, three of them
# alive at a time, while the caller's thread makes and drops three
# 598 KB numpy temporaries a chunk (a column copy and two staging
# temporaries of a 74,784-record batch).  One reader for all passes: a
# fresh thread a pass may land in an arena it has not been in, and the
# first 48 MiB touched there would be this test's noise.  Prints the
# minor faults of five passes (50 chunks) after two to warm up.
FAULT_LOOP = r'''
import collections, queue, resource, sys, threading
import numpy as np
sys.path.insert(0, sys.argv[1])
from dragnet_tpu import hostmem
if sys.argv[2] == 'hold':
    assert hostmem.hold_allocator() == (True, 'applied')
asks, chunks = queue.Queue(), queue.Queue()

def reader():
    with open('/dev/zero', 'rb') as f:
        while asks.get():
            chunks.put(f.read(1 << 24))

def one_pass(nchunks=10, alive=3):
    held = collections.deque()
    for _ in range(nchunks):
        asks.put(True)
        held.append(chunks.get())
        a = np.ones(74784)
        b = a * 2.0
        c = np.where(a > 1.0, a, b)
        del a, b, c
        if len(held) == alive:
            held.popleft()

def faults():
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt

t = threading.Thread(target=reader)
t.start()
one_pass()
one_pass()
before = faults()
for _ in range(5):
    one_pass()
print(faults() - before)
asks.put(False)
t.join()
'''


def fault_growth(mode):
    env = {k: v for k, v in os.environ.items() if k not in MALLOC_ENV}
    out = subprocess.run([sys.executable, '-c', FAULT_LOOP, REPO_ROOT, mode],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, timeout=300)
    assert out.returncode == 0, out.stderr.decode()
    return int(out.stdout.decode().split()[-1])


def test_held_heaps_fault_a_tenth_or_less():
    try:
        ctypes.CDLL(None).gnu_get_libc_version
    except (OSError, AttributeError):
        pytest.skip('not glibc: the policy is a no-op here (no_mallopt)')
    without, held = fault_growth('default'), fault_growth('hold')
    # glibc 2.36, 50 chunks: 61,280 without, 0 with
    assert without > 10000
    assert held * 10 < without
