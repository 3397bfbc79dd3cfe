"""Read the server's `metrics` op (Prometheus text of obs/metrics.py's
registry): sample name with sorted labels -> value."""

import re

_SAMPLE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')
PREFIX = 'dn_'


def parse(text):
    out = {}
    for line in text.splitlines():
        if not line or line.startswith('#'):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or '')))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            pass
    return out


def value(samples, name, labels=None):
    """One sample, by the registry's name (without the exposition's
    `dn_` prefix) and its labels; None when it was never written."""
    key = (PREFIX + name, tuple(sorted((labels or {}).items())))
    return samples.get(key)


def histogram_quantile(before, after, name, q, labels=None):
    """The upper bucket bound under which a share `q` of the window's
    observations fell (bucket counts are cumulative; the window's are
    the difference of two scrapes).  None with no observation."""
    want = dict(labels or {})
    rows = []
    for (n, lab), v in after.items():
        d = dict(lab)
        if n != PREFIX + name + '_bucket' or \
                {k: x for k, x in d.items() if k != 'le'} != want:
            continue
        le = float('inf') if d['le'] == '+Inf' else float(d['le'])
        rows.append((le, v - before.get((n, lab), 0.0)))
    rows.sort()
    if not rows or rows[-1][1] <= 0:
        return None
    need = q * rows[-1][1]
    for le, cum in rows:
        if cum >= need:
            return le
    return rows[-1][0]
