#!/usr/bin/env python3
"""dn: dragnet-tpu command-line interface."""

import time as _time
_T0 = _time.time()   # before any dragnet imports: the 'require' span

import os   # noqa: E402
import sys  # noqa: E402

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _root)

from dragnet_tpu.cli import main  # noqa: E402
_REQUIRE_S = _time.time() - _T0   # module-load cost (reference
                                  # bin/dn:80-83 tracked the same span)

# Lone surrogates (JSON \uD800-class escapes) must render rather than
# crash; Node's utf-8 encoder emits U+FFFD for them (not '?', which is
# what errors='replace' would produce).
import codecs  # noqa: E402


def _dn_fffd(err):
    # U+FFFD when the stream encoding can take it; '?' otherwise
    # (ASCII/C-locale stdout cannot encode the replacement char itself)
    # (as bytes: CPython's utf-8 encoder takes no non-ASCII str from
    # a handler, it raises "surrogates not allowed" all the same)
    try:
        rep = '�'.encode(err.encoding)
    except Exception:
        rep = '?'
    return (rep * (err.end - err.start), err.end)


codecs.register_error('dn_fffd', _dn_fffd)
for _stream in (sys.stdout, sys.stderr):
    try:
        _stream.reconfigure(errors='dn_fffd')
    except Exception:
        pass

if __name__ == '__main__':
    try:
        rv = main(startup=(_T0, _REQUIRE_S))
    except KeyboardInterrupt:
        rv = 130
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except BrokenPipeError:
        os._exit(0)
    sys.exit(rv)
