"""The plain reference: a numpy group-by over the generator's columns.

No JSON is parsed and nothing of the program is imported.  A query is
the document the client ships (`breakdowns`, optional `filter`,
`timeAfter`/`timeBefore` in epoch ms); the answer is the list of
`--points` lines the program must print, as sorted bytes, so a reply is
held to it tuple for tuple and count for count.

Counts are exact integers, and the table is sparse: occupied (part,
key) pairs only, so a breakdown by many-valued unbucketed columns costs
its tuples and not the product of their distinct values.

The controls of benchmarks/tests must NOT agree (a cell names its own
in `control`; the default is the first).  `accumulate='bfloat16'`: the
same group-by with the running sums rounded to bfloat16 after every
partial (a day's shard, or a 65,536-record batch), the precision a
later change might be tempted by.  `accumulate='key32'`: the same
group-by over the low 32 bits of the bit-packed key, the width a
sparse lane might be tempted by on a chip with no native int64; for a
cell whose counts are too small for bfloat16 to lose anything.
"""

import collections
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


# The occupied (part, key) pairs of one query's kept records, in part
# order: `part`, `key`, `count` a pair; `first` (a key's first kept
# record) and `prefixes` (its line prefix, once printed) a key; `cols`
# the breakdowns' (kept values, format); `base` the first part.
Table = collections.namedtuple(
    'Table', ['part', 'key', 'count', 'first', 'cols', 'prefixes', 'base'])


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

    def table(self, breakdowns, flt, part, fold32=False):
        """The `Table` of the records the filter keeps.  `part` is
        'day' (epoch day of the timestamp: what one daily shard holds)
        or 'batch' (65,536 records in corpus order: what one scan
        batch holds).  Nothing is sized by the product of the columns'
        distinct values, so a high-cardinality breakdown costs its
        tuples and no more.  `fold32` groups by the `key32` control's
        code instead of the exact one."""
        memo = json.dumps([breakdowns, flt, part, fold32], sort_keys=True)
        if memo in self._tables:
            return self._tables[memo]
        kept = np.flatnonzero(self.mask(flt))
        cols = []
        for b in breakdowns:
            vals, fmt = self._key(b)
            cols.append((vals[kept].astype(np.int64), fmt))
        code, bits = self._code(cols, len(kept))
        if fold32:
            # the `key32` control: only the low 32 bits of the key are
            # kept (the chip has no native int64: the shortcut a sparse
            # lane is tempted by), so records whose keys differ only
            # above bit 31 merge.  A key that fits 32 bits loses
            # nothing, the control could not fail: naming it is an error
            if bits <= 32:
                raise ValueError('key32 cannot fail here: the fused key '
                                 'is %d bits wide and fits 32' % bits)
            code &= 0xFFFFFFFF
        if part == 'day':
            parts = self.cols['ts_ms'] // DAY_MS
        else:
            parts = np.arange(self.n, dtype=np.int64) // BATCH
        base = int(parts.min())
        # a key's number is its rank among the kept records' codes
        _, first, key = np.unique(code, return_index=True,
                                  return_inverse=True)
        nkeys = len(first)
        pairs, counts = np.unique((parts[kept] - base) * nkeys + key,
                                  return_counts=True)
        rv = Table(pairs // nkeys, pairs % nkeys, counts, first, cols,
                   [None] * nkeys, base)
        self._tables[memo] = rv
        return rv

    @staticmethod
    def _code(cols, n):
        """(one integer a record, equal where every column is; its
        width in bits): each column's value, none below 0, in a bit
        field just wide enough for its largest, the fields
        concatenated."""
        code, bits = np.zeros(n, dtype=np.int64), 0
        for vals, _ in cols:
            width = max(1, int(vals.max()).bit_length()) if n else 1
            code = (code << width) | vals
            bits += width
        if bits > 62:
            raise ValueError('the fused key is %d bits wide' % bits)
        return code, bits

    # -- answers ---------------------------------------------------------

    def expected_lines(self, query, part='batch', accumulate='exact'):
        """The sorted `--points` lines for a query document.  Time
        bounds must fall on day boundaries (what the traffic sends);
        they select whole days of a 'day' table.  `accumulate` is
        'exact' or one of the controls, which must NOT agree:
        'bfloat16' rounds a key's running sum to bfloat16 after each
        of its parts, in part order; 'key32' groups by the low 32 bits
        of the bit-packed key, and a merged tuple prints under its
        first record's fields."""
        if accumulate not in ('exact', 'bfloat16', 'key32'):
            raise ValueError('accumulate: %r' % (accumulate,))
        breakdowns = query['breakdowns']
        t = self.table(breakdowns, query.get('filter'), part,
                       fold32=accumulate == 'key32')
        part_of, key_of, counts = t.part, t.key, t.count
        after, before = query.get('timeAfter'), query.get('timeBefore')
        if after is not None:
            if part != 'day' or after % DAY_MS or before % DAY_MS:
                raise ValueError('time bounds must be whole days of a '
                                 'day table')
            lo, hi = np.searchsorted(part_of, [after // DAY_MS - t.base,
                                               before // DAY_MS - t.base])
            part_of, key_of, counts = \
                part_of[lo:hi], key_of[lo:hi], counts[lo:hi]
        if accumulate == 'bfloat16':
            # a part in which a key has no record would add 0 and
            # round to the sum itself, so only a key's own parts count
            acc = np.zeros(len(t.first), dtype=np.float32)
            edges = np.flatnonzero(np.diff(part_of)) + 1
            for keys, cnt in zip(np.split(key_of, edges),
                                 np.split(counts, edges)):
                acc[keys] = round_bfloat16(
                    acc[keys] + cnt.astype(np.float32))
            total = acc.astype(np.int64)
        else:
            total = np.zeros(len(t.first), dtype=np.int64)
            np.add.at(total, key_of, counts)
        names = [json.dumps(b['name']) for b in breakdowns]
        lines = []
        for k in np.flatnonzero(total):
            if t.prefixes[k] is None:
                t.prefixes[k] = '{"fields":{%s},"value":' % ','.join(
                    '%s:%s' % (nm, fmt(vals[t.first[k]]))
                    for nm, (vals, fmt) in zip(names, t.cols))
            lines.append(('%s%d}' % (t.prefixes[k], total[k])).encode())
        lines.sort()
        return lines


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
