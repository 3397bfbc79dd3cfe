"""The process's allocator policy: glibc's malloc held to its heaps.

A scan asks malloc for large blocks at a steady rate: a 16 MiB `bytes`
per read (datasource_file._stream_native), a copy of every column of a
74,784-record batch (native.py: 598 KB a float64 column) and staging
temporaries of the same size (device_scan.py), from threads that are
new for every scan (the reader, the parser's producer) or every chunk
(the parser's own).  Left to itself glibc gives a heap's free top back
to the kernel past a trim threshold, deletes a thread arena's 64 MiB
heap once it is empty and maps blocks over its mmap threshold one by
one (it raises that threshold to the largest block freed so far, so
after the first chunk the chunks come from the heaps, and it is the
heaps that come and go).  Every page that comes back is a fault the
next time round and every unmap a TLB shootdown on all threads, under
the address space's one lock, which the parser's threads need for
their own faults: 19,634 minor faults a scan on a Linux host, 310 with
the policy (PERF.md section 6, PR 33).

hold_allocator() sets the policy once: such blocks come from the heaps,
what a heap has held it keeps, and there are few enough arenas that
all of them are warm after a few scans.  It is called by the `dn` entry
point (cli.main) and by nothing else: a program that only imports
dragnet_tpu keeps the allocator it configured.  What it does depends on
what it can observe and on no option of its own:

* no `mallopt` in the C library (musl, macOS): nothing, `no_mallopt`;
* the user's environment already speaks to glibc's malloc
  (`MALLOC_MMAP_THRESHOLD_`, `MALLOC_TRIM_THRESHOLD_`, `MALLOC_TOP_PAD_`,
  `MALLOC_ARENA_MAX`, or a `glibc.malloc.` entry in `GLIBC_TUNABLES`):
  nothing, `user_env`;
* glibc refuses a value (a 32-bit glibc caps the threshold at 512 KiB):
  `refused`.

The price is memory: a resident server keeps the heap its largest
request touched (docs/serving.md has the measured figures).
"""

import ctypes
import os

MIB = 1 << 20

# <malloc.h>
M_TRIM_THRESHOLD = -1
M_TOP_PAD = -2
M_MMAP_THRESHOLD = -3
M_ARENA_MAX = -8

# The values, each with what it is sized to.  None does the work alone
# (PERF.md section 6, PR 33: each alone is slower than glibc's own
# moving threshold, because setting any of them stops that movement and
# leaves the other two at their small defaults).
POLICY = (
    # the largest block the scan path asks for is the 16 MiB read chunk
    # plus its object header, so the threshold has to lie above 16 MiB;
    # 32 MiB is the most glibc takes (half of a thread arena's heap)
    (M_MMAP_THRESHOLD, 32 * MIB),
    # a heap's free top goes back to the kernel only beyond this.  A
    # thread arena's heap is 64 MiB at most, so at 64 MiB no such heap
    # is ever shrunk (the reader's arena holds three chunks, 48 MiB,
    # free between scans), and the main arena gives back what a request
    # freed beyond that
    (M_TRIM_THRESHOLD, 64 * MIB),
    # what a heap grows by beyond the request.  It also decides whether
    # an empty heap of a thread arena is deleted: only if the heap
    # before it has this much room, which at 64 MiB none has
    (M_TOP_PAD, 64 * MIB),
    # what is kept is kept per arena, and a scan's threads attach to
    # whichever arena is free.  Without a cap (glibc allows 8 a core,
    # and the device's runtime has threads of its own) the reader's
    # chunks warm one more arena with every scan: on the chip's host
    # 71 MB and 50 ms a scan, 3 GB after 85 scans and still rising.
    # With 8 every arena is warm after a few scans and the server holds
    # 0.5 GB; 4 and 16 scan as fast (the parser's threads allocate
    # little, so they do not queue for an arena) and hold 0.25 and 1.1
    (M_ARENA_MAX, 8),
)

USER_ENV = ('MALLOC_MMAP_THRESHOLD_', 'MALLOC_TRIM_THRESHOLD_',
            'MALLOC_TOP_PAD_', 'MALLOC_ARENA_MAX')

# (held, reason) once hold_allocator has run in this process
_state = None


def _mallopt():
    """The C library's `mallopt`, or None where it has none."""
    try:
        fn = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return None
    fn.argtypes = [ctypes.c_int, ctypes.c_int]
    fn.restype = ctypes.c_int
    return fn


def user_set(env):
    """True when `env` already configures glibc's malloc."""
    if any(env.get(name) for name in USER_ENV):
        return True
    return 'glibc.malloc.' in (env.get('GLIBC_TUNABLES') or '')


def hold_allocator():
    """Set the policy, once per process: (held, reason).  A second call
    changes nothing and returns what the first found."""
    global _state
    if _state is not None:
        return _state
    if user_set(os.environ):
        _state = (False, 'user_env')
        return _state
    mallopt = _mallopt()
    if mallopt is None:
        _state = (False, 'no_mallopt')
        return _state
    # mallopt returns 1 when it took the value
    taken = [mallopt(param, value) for param, value in POLICY]
    _state = (True, 'applied') if all(taken) else (False, 'refused')
    return _state


def state():
    """(held, reason) as hold_allocator found it, or None in a process
    where nothing has called it."""
    return _state
