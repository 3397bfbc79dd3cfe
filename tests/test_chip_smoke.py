"""chip_smoke.py rehearsed on XLA:CPU: every phase passes its byte
comparison and its engagement check, and the verdict is `ok: false` for
the single reason that the platform is not `tpu`; with the native
parser disabled the forced scans FAIL rather than answer from the
host."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ('scan-dense', 'scan-pallas', 'scan-sparse', 'build', 'query',
          'auto')


def _run(extra_env):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)      # one CPU device, as on one chip
    env.update(extra_env)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'chip_smoke.py'),
         '--records', '5000'],
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
