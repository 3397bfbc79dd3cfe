"""The one general traffic generator: a workload file's parameters and
a seed in, the window's requests out.

A request is (due seconds or None, template, window days or None, start
day or None).  Every seed gets the SAME sequence of classes (and, in an
open loop, the same arrivals): the counts of each template and window
class come from the shares by largest remainder, their order and the
Poisson gaps from the workload's own fixed `mix_seed`, and `--seed`
draws only the start days (and, in run.py, the corpus).  Near its capacity a server's tail is set by which
heavy requests meet which burst, so an order drawn from the seed made
the seed, not the program, decide a run's 95th percentile (measured in
PR 25: PERF.md section 6).
"""

import collections
import itertools
import random

Request = collections.namedtuple(
    'Request', ['due_s', 'template', 'days', 'start_day'])


def apportion(shares, n):
    """n items split by `shares` (largest remainder); sums to n."""
    total = float(sum(shares))
    exact = [n * s / total for s in shares]
    counts = [int(x) for x in exact]
    order = sorted(range(len(shares)),
                   key=lambda i: (counts[i] - exact[i], i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def classes(workload):
    """Every (template, window days) class with its share.  A workload
    without `windows` asks for the whole corpus."""
    windows = workload.get('windows') or [{'days': None, 'share': 1}]
    return [((t, w['days']), t.get('share', 1) * w['share'])
            for t in workload['templates'] for w in windows]


def _start_day(rng, days, corpus_days):
    if days is None:
        return None
    return rng.randrange(0, corpus_days - days + 1)


def open_loop(workload, seed, seconds, corpus_days):
    """`rate_per_s * seconds` requests with Poisson arrivals."""
    n = int(round(workload['rate_per_s'] * seconds))
    cls = classes(workload)
    counts = apportion([share for _, share in cls], n)
    picks = [c for (c, _), k in zip(cls, counts) for _ in range(k)]
    fixed = random.Random(workload.get('mix_seed', 1))
    gaps = [fixed.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps) if n else 0.0
    fixed.shuffle(picks)
    rng = random.Random(seed)
    out, due = [], 0.0
    for (template, days), gap in zip(picks, gaps):
        out.append(Request(due, template, days,
                           _start_day(rng, days, corpus_days)))
        due += gap * scale
    return out


def closed_loop(workload, seed, corpus_days):
    """An endless cycle of `cycle` requests (default: one of each
    class), the classes in their shares and in the `mix_seed`'s order;
    the clients take requests from it until the window is over."""
    cls = classes(workload)
    counts = apportion([share for _, share in cls],
                       workload.get('cycle', len(cls)))
    picks = [c for (c, _), k in zip(cls, counts) for _ in range(k)]
    random.Random(workload.get('mix_seed', 1)).shuffle(picks)
    rng = random.Random(seed)
    for template, days in itertools.cycle(picks):
        yield Request(None, template, days,
                      _start_day(rng, days, corpus_days))


def warmup(workload):
    """One request of each class, the longest-reaching start first: the
    shapes the window will use, and no others."""
    return [Request(None, t, days, 0 if days is not None else None)
            for (t, days), _ in classes(workload)]
