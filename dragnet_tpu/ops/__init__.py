"""Vectorized kernels for the scan hot path.

The reference's per-record hot loop (JSON.parse -> predicate.eval ->
Date.parse -> hash update, one JS callback round-trip per record per stage;
see SURVEY.md §3.1) becomes, per columnar batch:

* predicate -> 3-state mask fold,
* bucketize -> elementwise power-of-two / linear kernels,
* group-by  -> mixed-radix key fusion + segment-sum,

all in ops/kernels.py (jax.numpy, jit) with Pallas/Mosaic variants of the
hot kernels in ops/pallas_kernels.py.

Kernels are written against jax.numpy and jit-compiled (MXU/VPU on TPU;
XLA:CPU in tests), with semantics pinned to the host reference
implementation in aggr.py/scan.py by differential tests.

jax is imported lazily and 64-bit mode is enabled on first use: epoch
seconds and latencies exceed float32's exact-integer range, so bucket
arithmetic must run in f64/i64.
"""

import os

_jax = None

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir():
    """The one directory where compiled programs and the audition
    verdicts persist.  Where JAX_COMPILATION_CACHE_DIR is set it is
    that directory — jax reads the variable itself, so the program
    sets no cache path in code — and otherwise a fixed path inside the
    checkout (the path is part of the cache key: a directory that
    moves never hits)."""
    return os.environ.get('JAX_COMPILATION_CACHE_DIR') or \
        os.path.join(_REPO_ROOT, '.cache', 'xla')


def get_jax():
    """Import jax on demand with x64 enabled; returns (jax, jnp), or
    None when jax is not installed.  An installed jax that fails to
    import or to take its configuration raises: that is a broken
    installation, not "no jax".  Deliberately does NOT touch the
    backend: multi-process launches must call
    jax.distributed.initialize before any backend-initializing call.
    Callers that need live devices use backend_ready()."""
    global _jax
    if _jax is None:
        try:
            import jax
        except ModuleNotFoundError as e:
            if e.name != 'jax':
                raise
            _jax = False
            return None
        jax.config.update('jax_enable_x64', True)
        if os.environ.get('DN_XLA_CACHE', '1') != '0':
            # persistent XLA compile cache: a CLI process pays the
            # compile of a scan program only once per (query shape,
            # backend), not per invocation
            if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
                jax.config.update('jax_compilation_cache_dir',
                                  cache_dir())
            # cache real compiles but not every sub-millisecond
            # variant — the persistent cache has no eviction of its own
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', 0.2)
            jax.config.update(
                'jax_persistent_cache_min_entry_size_bytes', -1)
        import jax.numpy as jnp
        from ..obs import metrics as obs_metrics
        obs_metrics.watch_compiles(jax)
        _jax = (jax, jnp)
    return _jax if _jax else None


_backend_ready = None


def backend_ready():
    """True when jax's platform actually initializes (e.g. False when
    JAX_PLATFORMS names a platform this machine does not have) — the
    gate for device execution paths in auto mode to degrade to the
    host engine instead of crashing.

    NOTE: the first call fully initializes the backend (about 15 s for
    a process to reach a directly attached TPU).  Callers on
    latency-sensitive paths must consult platform_hint() first and
    defer this probe until device execution is actually wanted (see
    device_scan.scan_class)."""
    global _backend_ready
    if _backend_ready is None:
        j = get_jax()
        if j is None:
            _backend_ready = False
        else:
            try:
                j[0].devices()
                _backend_ready = True
            except Exception:
                _backend_ready = False
    return _backend_ready


def backend_probed():
    """The cached backend_ready() verdict WITHOUT probing: True/False
    when a probe already ran this process, None when unknown.  For
    informational paths (e.g. dry-run plans) that must never pay
    backend initialization."""
    return _backend_ready


def platform_hint():
    """Cheap, non-backend-initializing guess at the jax platform: the
    first entry of JAX_PLATFORMS ('' when unset, meaning jax would
    auto-select).  Used to route small scans to the host engine without
    paying backend initialization (seconds, on a chip)."""
    import os
    return (os.environ.get('JAX_PLATFORMS') or '').split(',')[0] \
        .strip().lower()


def accelerator_likely():
    """Whether an accelerator backend is plausibly present, WITHOUT
    initializing it: a non-cpu JAX_PLATFORMS entry, or, when unset, a
    libtpu install that jax's auto-selection would pick up.  The device
    path re-checks with is_accelerator() (a real probe) before running."""
    hint = platform_hint()
    if hint:
        return hint != 'cpu'
    import importlib.util
    try:
        return importlib.util.find_spec('libtpu') is not None
    except (ImportError, ValueError):
        return False


def device_platform():
    """Platform name of jax's default backend ('cpu', 'tpu', ...), or
    None when no backend initializes.  Initializes the
    backend — see the backend_ready() latency note."""
    if not backend_ready():
        return None
    jax, _ = get_jax()
    try:
        return jax.default_backend()
    except Exception:
        return None


def is_accelerator():
    """True when the default backend is a live accelerator — anything
    other than XLA:CPU: the capability that matters for routing
    batches to the device."""
    p = device_platform()
    return p is not None and p != 'cpu'


def is_tpu_backend():
    """True when the default backend's devices are TPU chips — i.e.
    Mosaic can compile Pallas kernels for them."""
    return device_platform() == 'tpu'
