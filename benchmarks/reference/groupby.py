"""The plain reference: a numpy group-by over the generator's columns.

No JSON is parsed and nothing of the program is imported.  A query is
the document the client ships (`breakdowns`, optional `filter`,
`timeAfter`/`timeBefore` in epoch ms); the answer is the list of
`--points` lines the program must print, as sorted bytes, so a reply is
held to it tuple for tuple and count for count.

Counts are exact integers.  `accumulate='bfloat16'` is the control of
benchmarks/tests: the same group-by with the running sums rounded to
bfloat16 after every partial (a day's shard, or a 65,536-record batch),
the precision a later change might be tempted by.  It must NOT agree.
"""

import json

import numpy as np

DAY_MS = 86400000
BATCH = 65536

# field path -> (column, kind); kind 'enum:<list>' prints the list's
# string, 'number' prints the integer as a string (the program prints
# an unbucketized number as a string), 'url' the generator's path
FIELDS = {
    'host': ('host', 'enum'),
    'req.method': ('method', 'enum'),
    'operation': ('op', 'enum'),
    'req.url': ('url', 'url'),
    'res.statusCode': ('status', 'number'),
    'latency': ('latency', 'number'),
    'dataLatency': ('dlatency', 'number'),
    'dataSize': ('dsize', 'number'),
}


def quantize(values):
    """Power-of-two buckets: the largest power of two <= v, and 0 for
    v < 1."""
    v = np.asarray(values, dtype=np.int64)
    out = np.zeros_like(v)
    pos = v >= 1
    # exact for v < 2**53: floor(log2) of an integer held in a double
    out[pos] = np.int64(1) << np.floor(
        np.log2(v[pos].astype(np.float64))).astype(np.int64)
    return out


def lquantize(values, step):
    """Linear buckets: v rounded down to a multiple of `step`."""
    v = np.asarray(values, dtype=np.int64)
    return (v // step) * step


def round_bfloat16(x):
    """float32 values rounded to the nearest bfloat16 (ties to even)."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


class Reference(object):
    def __init__(self, cols, enums):
        """`cols`: the generator's columns; `enums`: column name ->
        list of strings, for the columns that hold an index."""
        self.cols = cols
        self.enums = enums
        self.n = len(cols['ts_ms'])
        self._tables = {}

    # -- filters ---------------------------------------------------------

    def _operand(self, field, const):
        """(column values, constant in the column's terms); None as the
        constant when no record can equal it."""
        col, kind = FIELDS[field]
        vals = self.cols[col]
        if kind == 'enum':
            names = self.enums[col]
            return vals, (names.index(const) if const in names else None)
        if kind == 'url':
            raise ValueError('no filter on "%s" in the reference' % field)
        return vals, const

    def mask(self, flt):
        """Boolean mask of the records a filter document keeps."""
        if flt is None:
            return np.ones(self.n, dtype=bool)
        (op, args), = flt.items()
        if op == 'and':
            return np.logical_and.reduce([self.mask(a) for a in args])
        if op == 'or':
            return np.logical_or.reduce([self.mask(a) for a in args])
        vals, const = self._operand(args[0], args[1])
        if const is None:
            hit = np.zeros(self.n, dtype=bool)
            return ~hit if op == 'ne' else hit
        return {'eq': np.equal, 'ne': np.not_equal, 'lt': np.less,
                'le': np.less_equal, 'gt': np.greater,
                'ge': np.greater_equal}[op](vals, const)

    # -- keys ------------------------------------------------------------

    def _key(self, b):
        """(values per record, function value -> the JSON text the
        program prints for it) of one breakdown."""
        col, kind = FIELDS[b.get('field') or b['name']]
        vals = self.cols[col]
        aggr = b.get('aggr')
        if aggr == 'quantize':
            return quantize(vals), lambda v: '%d' % v
        if aggr == 'lquantize':
            return lquantize(vals, int(b['step'])), lambda v: '%d' % v
        if aggr is not None:
            raise ValueError('aggr "%s" not in the reference' % aggr)
        if kind == 'enum':
            names = self.enums[col]
            return vals, lambda v: json.dumps(names[v])
        if kind == 'url':
            return vals, lambda v: '"/random/url/number/%d"' % v
        return vals, lambda v: '"%d"' % v

    def table(self, breakdowns, flt, part):
        """counts[part, key] and each key's line prefix, for the
        records the filter keeps.  `part` is 'day' (epoch day of the
        timestamp: what one daily shard holds) or 'batch' (65,536
        records in corpus order: what one scan batch holds)."""
        memo = json.dumps([breakdowns, flt, part], sort_keys=True)
        if memo in self._tables:
            return self._tables[memo]
        keep = self.mask(flt)
        code = np.zeros(self.n, dtype=np.int64)
        radix = []
        for b in breakdowns:
            vals, fmt = self._key(b)
            uniq, inv = np.unique(vals, return_inverse=True)
            code = code * len(uniq) + inv
            radix.append((uniq, fmt))
        nkeys = 1
        for uniq, _ in radix:
            nkeys *= len(uniq)
        if part == 'day':
            parts = self.cols['ts_ms'] // DAY_MS
        else:
            parts = np.arange(self.n, dtype=np.int64) // BATCH
        base = int(parts.min())
        nparts = int(parts.max()) - base + 1
        flat = (parts[keep] - base) * nkeys + code[keep]
        counts = np.bincount(flat, minlength=nparts * nkeys) \
            .reshape(nparts, nkeys)
        names = [json.dumps(b['name']) for b in breakdowns]
        prefixes = []
        for k in range(nkeys):
            digits, rest = [], k
            for uniq, fmt in reversed(radix):
                digits.append(fmt(uniq[rest % len(uniq)]))
                rest //= len(uniq)
            prefixes.append('{"fields":{%s},"value":' % ','.join(
                '%s:%s' % (nm, d)
                for nm, d in zip(names, reversed(digits))))
        rv = (counts, prefixes, base)
        self._tables[memo] = rv
        return rv

    # -- answers ---------------------------------------------------------

    def expected_lines(self, query, part='batch', accumulate='exact'):
        """The sorted `--points` lines for a query document.  Time
        bounds must fall on day boundaries (what the traffic sends);
        they select whole days of a 'day' table."""
        counts, prefixes, base = self.table(
            query['breakdowns'], query.get('filter'), part)
        after, before = query.get('timeAfter'), query.get('timeBefore')
        if after is not None:
            if part != 'day' or after % DAY_MS or before % DAY_MS:
                raise ValueError('time bounds must be whole days of a '
                                 'day table')
            lo = max(0, after // DAY_MS - base)
            hi = max(lo, min(len(counts), before // DAY_MS - base))
            counts = counts[lo:hi]
        if accumulate == 'exact':
            total = counts.sum(axis=0)
        elif accumulate == 'bfloat16':
            acc = np.zeros(counts.shape[1], dtype=np.float32)
            for row in counts:
                acc = round_bfloat16(acc + row.astype(np.float32))
            total = acc.astype(np.int64)
        else:
            raise ValueError('accumulate: %r' % (accumulate,))
        return sorted(('%s%d}' % (prefixes[k], total[k])).encode()
                      for k in np.flatnonzero(total))


def compare(reply, expected):
    """(tuples that differ, sum of |count differences|) between a
    reply's `--points` bytes and the expected sorted lines.  Both 0
    when the reply is exact.  The fast path never parses a line."""
    got = sorted(ln for ln in reply.split(b'\n') if ln)
    if got == expected:
        return 0, 0

    def as_dict(lines):
        d = {}
        for ln in lines:
            head, _, val = ln.rpartition(b'"value":')
            try:
                d[head] = d.get(head, 0) + int(val.rstrip(b'}'))
            except ValueError:
                d[ln] = None
        return d

    g, e = as_dict(got), as_dict(expected)
    ntuples = delta = 0
    for k in set(g) | set(e):
        a, b = g.get(k), e.get(k)
        if a != b:
            ntuples += 1
            delta += abs((a or 0) - (b or 0))
    return ntuples, delta
