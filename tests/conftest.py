import os
import sys
import tempfile

# Multi-device tests run on a virtual 8-device CPU mesh.  Nothing in
# this installation imports jax before this conftest runs, so the
# environment alone decides; force (not setdefault) so that the suite
# runs on the CPU mesh whatever the caller exported.
os.environ['JAX_PLATFORMS'] = 'cpu'
xla_flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in xla_flags:
    os.environ['XLA_FLAGS'] = (
        xla_flags + ' --xla_force_host_platform_device_count=8').strip()

# Hermeticity: the audition-verdict cache persists routing decisions
# in the compile-cache directory between CLI runs by design, but tests
# that stage wins/losses (test_auto_mode) must never see verdicts from
# a previous test or a previous run.  Tests that exercise the cache
# itself opt back in with DN_AUDITION_CACHE=1 and a tmp
# JAX_COMPILATION_CACHE_DIR.
os.environ['DN_AUDITION_CACHE'] = '0'

# Hermeticity: a DnServer's ResourceGovernor reads the machine's disk
# and under DN_DISK_LOW_PCT free pauses repair pulls and handoff
# fetches; pin it at 50% free through the sim-file hook (children
# inherit it, test_resources.py replaces it).
_disk_sim = os.path.join(tempfile.mkdtemp(prefix='dn_test_disk_'),
                         'free_pct')
with open(_disk_sim, 'w') as _f:
    _f.write('50\n')
os.environ['DN_DISK_SIM_FILE'] = _disk_sim

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
