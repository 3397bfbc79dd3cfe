"""Group-by aggregation with skinner-compatible semantics.

Re-implements the behavior of the reference's `skinner` dependency (Joyent
node-skinner, #dragnet branch) as used via queryAggrStream
(reference: lib/dragnet-impl.js:48-89):

* decomposition fields are looked up with jsprim-pluck semantics,
* bucketized fields must be JS numbers; anything else drops the record,
* non-bucketized field values are keyed by String(v) — null -> "null",
  missing -> "undefined", numbers -> their decimal string (this is why
  `dn scan -b req.caller` shows "null"/"undefined" rows in the goldens),
* buckets are tracked as ordinal indexes internally (`ordinalBuckets`),
  but emitted points carry bucket-minimum values so that point streams
  re-aggregate idempotently (the map/reduce wire-format seam),
* emission order follows JS object property order: integer-like keys
  ascending first, then string keys in insertion order.

This host-side implementation is the semantic reference; the vectorized
paths (engine.py and ops/kernels.py) compute identical (key -> weight)
maps for columnar batches and merge into the same flat structure.
"""

import numpy as np

from . import jsvalues as jsv
from .obs import metrics as obs_metrics


def _unique_rows_2(a, b):
    """np.unique(return_index/inverse) over 2 int64 columns when their
    fused span overflows int64 (degenerate; row-wise unique instead)."""
    mat = np.stack([a, b], axis=1)
    _, first_idx, inv = np.unique(mat, axis=0, return_index=True,
                                  return_inverse=True)
    return first_idx, inv.reshape(-1), None


def _unique_1d(vals, span):
    """np.unique(return_index/inverse) for non-negative int64 codes in
    [0, span): dense first-occurrence tables in O(n + span) when the
    span is comparable to n, sort-based otherwise.  Returns
    (first_idx, inv) with uniques implicitly in ascending code order —
    exactly np.unique's contract."""
    n = len(vals)
    if 0 < span <= max(65536, 4 * n):
        # reversed fancy assignment: duplicate indexes write last-wins,
        # so feeding rows in reverse leaves each code's FIRST occurrence
        first = np.full(span, -1, dtype=np.int64)
        first[vals[::-1]] = np.arange(n - 1, -1, -1)
        ids = np.flatnonzero(first >= 0)
        rank = np.empty(span, dtype=np.int64)
        rank[ids] = np.arange(len(ids))
        return first[ids], rank[vals]
    _, first_idx, inv = np.unique(vals, return_index=True,
                                  return_inverse=True)
    return first_idx, inv.reshape(-1)


def _is_array_index(s):
    if not s or not s.isdigit():
        return False
    if len(s) > 1 and s[0] == '0':
        return False
    return int(s) < 2 ** 32 - 1


def _key_ranks(values):
    """Per entry of a column's dictionary, its key's rank in JS
    enumeration among the dictionary's numeric-class keys
    (array-index-like strings and Python ints, ascending by value,
    equal values sharing a rank) and -1 for every other key; and how
    many ranks there are.  A table over the dictionary, not over the
    tuples."""
    table = np.full(len(values), -1, dtype=np.int64)
    idx = []
    vals = []
    for i, s in enumerate(values):
        if isinstance(s, str):
            if _is_array_index(s):
                idx.append(i)
                vals.append(int(s))
        elif isinstance(s, int) and not isinstance(s, bool):
            idx.append(i)
            vals.append(s)
    if not idx:
        return table, 0
    uniq, inv = np.unique(np.array(vals, dtype=np.int64),
                          return_inverse=True)
    table[idx] = inv.reshape(-1)
    return table, len(uniq)


def coerce_bucket_value(v):
    """The JS numeric coercion bucketized fields apply before
    bucketize(): numeric strings coerce (the fixture data plants a
    latency of "26" to pin this), anything non-coercible returns None
    (drop the record).  THE single definition of the drop rule — the
    per-record write() path, the DNC fast lane (_execute_keys), and
    the stacked cross-shard path (index_query_stack) must agree on it
    exactly, or their outputs diverge."""
    if isinstance(v, str):
        fv = jsv.to_number(v)
        if fv != fv:
            return None
        return int(fv) if fv == int(fv) else fv
    if not jsv.is_number(v):
        return None
    return v


def js_key_order(keys):
    """Order keys the way V8 enumerates own properties: array-index-like
    keys ascending, then the rest in insertion order."""
    ints = []
    rest = []
    for k in keys:
        if isinstance(k, int):
            ints.append(k)
        elif _is_array_index(k):
            ints.append(k)
        else:
            rest.append(k)
    ints.sort(key=lambda k: int(k))
    return ints + rest


def _object_array(values):
    """`values` as a 1-d object array: what a code column gathers
    through, so the exact Python objects (int against float against
    str) reach the output."""
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return arr


def _gather(codes, table):
    """The column `codes` name in `table`, or `codes` themselves where
    there is no table (rows() carries a bucketized field's ordinals)."""
    if table is None:
        return codes.tolist()
    return _object_array(table)[codes].tolist()


class PointBlock(object):
    """A columnar result in points() emission order, without the
    per-point dicts: per decomposition an int64 code column and the
    table of that column's values exactly as points() emits them
    (bucket minima for bucketized fields, the dictionary's values
    otherwise; a table may hold values no code names), and the
    weights as points() carries them.  output.print_points formats a
    block by column; whoever wants the dicts asks points(), which
    builds the list points() returned before there were blocks, once."""

    __slots__ = ('names', 'codes', 'tables', 'weights', '_points')

    def __init__(self, names, codes, tables, weights):
        self.names = names
        self.codes = codes
        self.tables = tables
        self.weights = weights
        self._points = None

    def __len__(self):
        return len(self.weights)

    def columns(self):
        """The decoded key columns (point_rows()'s)."""
        return [_gather(c, t) for c, t in zip(self.codes, self.tables)]

    def points(self):
        if self._points is None:
            self._points = self._make_points()
        return self._points

    def _make_points(self):
        cols_out = self.columns()
        names = self.names
        # literal dict construction (dict(zip(...)) costs ~2x here),
        # and tuples built by a second zip pass rather than inside the
        # comprehension (measured ~3x faster on CPython 3.12 at
        # hundreds of thousands of tuples)
        if len(names) == 1:
            n0, = names
            fields = [{n0: a} for a in cols_out[0]]
        elif len(names) == 2:
            n0, n1 = names
            fields = [{n0: a, n1: b}
                      for a, b in zip(cols_out[0], cols_out[1])]
        elif len(names) == 3:
            n0, n1, n2 = names
            fields = [{n0: a, n1: b, n2: c} for a, b, c
                      in zip(cols_out[0], cols_out[1], cols_out[2])]
        else:
            fields = [dict(zip(names, t)) for t in zip(*cols_out)]
        return list(zip(fields, self.weights))

    def text_size(self):
        """About the characters print_points writes for this block,
        from its arrays (the result cache's size estimate)."""
        # {"fields":{ ... },"value":N}\n and "name":"value", a column
        size = len(self) * (26 + sum(len(name) + 6
                                     for name in self.names))
        for codes, table in zip(self.codes, self.tables):
            lens = np.fromiter((len(str(v)) for v in table),
                               dtype=np.int64, count=len(table))
            size += int(lens[codes].sum())
        return size


class Aggregator(object):
    def __init__(self, query, stage=None):
        self.decomps = [b['name'] for b in query.qc_breakdowns]
        self.bucketizers = query.qc_bucketizers
        self.stage = stage
        # flat map: key tuple -> weight, insertion-ordered (Python
        # dicts preserve it); the nested JS-object view is built once
        # at walk time — one dict op per write instead of one per level
        self.flat = {}
        self.total = 0  # the no-decomposition case
        self.nrecords = 0
        # columnar result (set_columnar): code arrays + weights in
        # first-occurrence order; high-cardinality scans skip the
        # per-tuple flat-dict writes entirely
        self._cols = None
        self._cweights = None
        self._cdec = None

    def write(self, fields, value):
        if self.stage is not None:
            self.stage.bump('ninputs')
        keys = []
        for name in self.decomps:
            v = jsv.pluck(fields, name)
            if name in self.bucketizers:
                v = coerce_bucket_value(v)
                if v is None:
                    if self.stage is not None:
                        self.stage.warn(
                            ValueError('value for field "%s" is not a '
                                       'number' % name), 'nnonnumeric')
                    return
                keys.append(self.bucketizers[name].bucketize(v))
            else:
                keys.append(jsv.to_string(v))
        self._add(tuple(keys), value)

    def write_key(self, keys, value):
        """Add a pre-computed key tuple (ordinals for bucketized fields,
        strings otherwise) — the entry point for the vectorized path."""
        self._add(tuple(keys), value)

    def _add(self, keys, value):
        if self._cols is not None:
            # the columnar result is final; a write after conversion
            # would be silently invisible to points()/rows()
            raise RuntimeError(
                'Aggregator.write after columnar conversion')
        self.nrecords += 1
        if not self.decomps:
            self.total += value
            return
        flat = self.flat
        flat[keys] = flat.get(keys, 0) + value

    def set_columnar(self, cols, weights, decoders):
        """Install the aggregate as parallel code columns instead of
        per-tuple flat-dict writes (the vectorized engines' deferred
        merge hands its unique tuples here): `cols` are int64 arrays in
        first-occurrence order — engine string-dictionary codes for
        plain columns, raw ordinals for bucketized ones — `weights`
        float64, `decoders` one ('str', values_list) or ('ord', None)
        per decomp.  points()/rows() then order and decode columnarly;
        Python-object work becomes O(output tuples), once.

        Requires an empty flat map (callers merge any flat prefix into
        the columns first) and replaces it entirely."""
        assert not self.flat and len(cols) == len(self.decomps)
        self._cols = [np.asarray(c, dtype='int64') for c in cols]
        if isinstance(weights, list):
            self._cweights = weights     # exact Python numbers
        else:
            self._cweights = np.asarray(weights, dtype='float64')
        self._cdec = decoders

    # results at least this large take the columnar order/decode even
    # when they arrived as per-tuple flat writes (the MT merge path):
    # the nested-dict walk is the dominant cost of emitting a
    # high-cardinality result
    FLAT_COLUMNAR_MIN = 8192

    def _flat_to_columnar(self):
        """Convert the flat map to columns (first-occurrence order is
        the dict's insertion order) so points()/rows() vectorize."""
        cols = [[] for _ in self.decomps]
        encs = []
        decoders = []
        for name in self.decomps:
            if name in self.bucketizers:
                encs.append(None)
                decoders.append(('ord', None))
            else:
                vals = []
                encs.append(({}, vals))
                decoders.append(('str', vals))
        weights = []
        for keys, w in self.flat.items():
            for col, enc, k in zip(cols, encs, keys):
                if enc is None:
                    col.append(k)
                else:
                    index, vals = enc
                    c = index.get(k)
                    if c is None:
                        c = len(vals)
                        index[k] = c
                        vals.append(k)
                    col.append(c)
            weights.append(w)
        self.flat = {}
        self.set_columnar([np.asarray(c, dtype=np.int64) for c in cols],
                          weights, decoders)

    def _columnar_order(self):
        """JS property-enumeration order over the columnar tuples,
        vectorized.  Per level, a key's rank is (numeric-likeness,
        int value) for array-index-like keys and (non-numeric,
        first-occurrence-within-parent) otherwise — exactly the
        js_key_order applied at every node of the nested walk.  The
        within-parent arrival rank is the first occurrence index of
        the (parent-group, code) pair in arrival order.  Each level
        becomes ONE non-negative rank column (numeric keys by their
        rank among the dictionary's values, the others after them by
        arrival), and a stable sort over the levels reproduces the
        nested enumeration: one argsort of the fused mixed-radix key
        (engine.fuse_codes), a lexsort over the same columns where
        their spans' product would overflow it."""
        from .engine import fuse_codes
        n = len(self._cweights)
        if not n:
            return np.zeros(0, dtype=np.int64)
        # per level: the rank column, which tuples hold a non-numeric
        # key (None: none does) and how many numeric ranks precede them
        levels = []
        grouped = -1    # the last level that holds a non-numeric key
        for codes, dec in zip(self._cols, self._cdec):
            if dec[0] == 'ord':
                # int keys: all numeric-class, ascending by value
                levels.append((codes, None, 0))
                continue
            table, nnum = _key_ranks(dec[1])
            rank = table[codes]
            nn = rank < 0 if nnum < len(table) else None
            if nn is not None and nn.any():
                grouped = len(levels)
            else:
                nn = None
            levels.append((rank, nn, nnum))
        # the (parent group, code) grouping, only as deep as a
        # non-numeric key reads it: its own level's for its arrival
        # rank, the levels' above for its parent group
        keys = []
        gid = None
        ngroups = 1
        for depth, (rank, nn, nnum) in enumerate(levels):
            if depth <= grouped:
                codes = self._cols[depth]
                if self._cdec[depth][0] == 'ord':
                    lo = int(codes.min())
                    span = int(codes.max()) - lo + 1
                    pair_code = codes - lo
                else:
                    span = len(self._cdec[depth][1])
                    pair_code = codes
                if gid is None:
                    first_idx, inv = _unique_1d(pair_code, span)
                elif ngroups * span < 2 ** 62:
                    first_idx, inv = _unique_1d(gid * span + pair_code,
                                                ngroups * span)
                else:
                    first_idx, inv, _ = _unique_rows_2(gid, pair_code)
                if nn is not None:
                    rank = np.where(nn, first_idx[inv] + nnum, rank)
                gid = inv
                ngroups = len(first_idx)
            keys.append(rank)
        fused = fuse_codes(keys)        # most significant first
        if fused is None:
            obs_metrics.inc('aggr_order_total', path='lexsort')
            return np.lexsort(tuple(reversed(keys)))
        obs_metrics.inc('aggr_order_total', path='fused')
        return np.argsort(fused, kind='stable')

    def _columnar(self):
        """True when the result is columnar: an engine handed it code
        columns (set_columnar), or the flat map has reached
        FLAT_COLUMNAR_MIN tuples and is converted here."""
        if self._cols is None and \
                len(self.flat) >= self.FLAT_COLUMNAR_MIN:
            self._flat_to_columnar()
        return self._cols is not None

    def _columnar_cols(self, as_rows):
        """The ordered output columns + weights (the shared tail of
        points()/rows()/point_rows()/point_block()): per decomposition
        the ordered code column and the table its codes index —
        bucket-min values for bucketized fields, and no table (the
        codes are the ordinals rows carry) when as_rows."""
        order = self._columnar_order()
        codes_out = []
        tables = []
        for codes, dec, name in zip(self._cols, self._cdec,
                                    self.decomps):
            cc = codes[order]
            if dec[0] != 'ord':
                codes_out.append(cc)
                tables.append(dec[1])
            elif as_rows:
                # rows carry ordinal form, not bucket-min
                codes_out.append(cc)
                tables.append(None)
            else:
                # bucket-min per unique ordinal (few): the exact
                # Python values bucket_min returned (int vs float)
                # survive to the output
                bz = self.bucketizers[name]
                uniq, inv = np.unique(cc, return_inverse=True)
                codes_out.append(inv.reshape(-1))
                tables.append([bz.bucket_min(int(o)) for o in uniq])
        if isinstance(self._cweights, list):
            # flat->columnar conversion keeps the exact stored Python
            # numbers (no f64 round trip)
            ol = order.tolist()
            weights = [self._cweights[i] for i in ol]
        else:
            wo = self._cweights[order]
            if len(wo) and np.all(wo == np.floor(wo)) and \
                    np.all(np.abs(wo) <= 2 ** 53):
                # the usual case: all-integral weights convert at C
                # speed instead of per-element is_integer() checks
                weights = wo.astype(np.int64).tolist()
            else:
                weights = [int(w) if w.is_integer() else w
                           for w in wo.tolist()]
        return codes_out, tables, weights

    def point_block(self):
        """The columnar aggregate as a PointBlock (points() without
        the per-point dicts), or None when the aggregate is not
        columnar.  Stage counters bump as points() bumps them."""
        if not self._columnar():
            return None
        codes, tables, weights = self._columnar_cols(False)
        if self.stage is not None:
            self.stage.bump('noutputs', len(weights))
        return PointBlock(self.decomps, codes, tables, weights)

    def _columnar_points(self, as_rows):
        if not as_rows:
            return self.point_block().points()
        # (rows() never bumped noutputs on the flat path either)
        codes, tables, weights = self._columnar_cols(True)
        if not codes:
            return [list(t) for t in zip(weights)]
        cols_out = [_gather(c, t) for c, t in zip(codes, tables)]
        return [list(t) + [w]
                for t, w in zip(zip(*cols_out), weights)]

    def _walk(self):
        """Yield (keys_tuple, weight) in JS property-enumeration order.

        The nested dict is materialized from the flat map here: each
        level's key insertion order equals the first occurrence of any
        tuple with that prefix, exactly as per-write nested insertion
        produced."""
        if not self.decomps:
            yield ((), self.total)
            return

        root = {}
        for keys, weight in self.flat.items():
            node = root
            for k in keys[:-1]:
                nxt = node.get(k)
                if nxt is None:
                    nxt = {}
                    node[k] = nxt
                node = nxt
            node[keys[-1]] = weight

        def rec(node, depth, prefix):
            if depth == len(self.decomps):
                yield (tuple(prefix), node)
                return
            for k in js_key_order(node.keys()):
                prefix.append(k)
                for item in rec(node[k], depth + 1, prefix):
                    yield item
                prefix.pop()

        for item in rec(root, 0, []):
            yield item

    def key_items(self):
        """(keys_tuple, weight) pairs in first-occurrence order — the
        transferable wire format of this aggregate (the index-shard
        fan-out).  Replaying the pairs into another Aggregator for the
        same query via write_key() merges byte-identically to
        re-writing points():

        * keys round-trip exactly (bucketize(bucket_min(i)) == i for
          both bucketizers; non-bucketized keys are already to_string'd)
        * emitting insertion order instead of points()'s _walk order
          cannot change the receiver's output, because the receiver
          re-walks: integer-like keys re-sort numerically regardless of
          insertion order, and the relative first-occurrence order of
          the remaining (string-like) keys is the same under both
          emission orders.
        """
        assert self._cols is None, 'key_items after columnar conversion'
        if not self.decomps:
            return [((), self.total)]
        return list(self.flat.items())

    def merge_key_items(self, items):
        """Bulk write_key: replay a key_items() transfer into this
        aggregate (the index-shard fan-in's hot loop — one dict upsert
        per pair, no per-pair method call)."""
        if self._cols is not None:
            raise RuntimeError(
                'Aggregator.write after columnar conversion')
        self.nrecords += len(items)
        if not self.decomps:
            for _, value in items:
                self.total += value
            return
        flat = self.flat
        get = flat.get
        for keys, value in items:
            flat[keys] = get(keys, 0) + value

    def point_rows(self):
        """The aggregate as columnar point blocks: (key columns,
        weights) in points() emission order with bucketized fields
        decoded to bucket-min values — exactly points() without the
        per-point field dicts.  The index build consumes these blocks
        directly (index_build_mt.write_index_blocks); stage counters
        bump identically to points() so --counters output is
        unchanged."""
        block = self.point_block()
        if block is not None:
            return block.columns(), block.weights
        if not self.decomps:
            if self.stage is not None:
                self.stage.bump('noutputs')
            return [], [self.total]
        cols = [[] for _ in self.decomps]
        weights = []
        decs = [self.bucketizers.get(name) for name in self.decomps]
        nout = 0
        for keys, weight in self._walk():
            for col, bz, k in zip(cols, decs, keys):
                col.append(bz.bucket_min(k) if bz is not None else k)
            weights.append(weight)
            nout += 1
        if self.stage is not None and nout:
            self.stage.bump('noutputs', nout)
        return cols, weights

    def points(self):
        """Aggregated points: fields carry bucket-min values for bucketized
        fields (re-ingestable), strings otherwise."""
        if self._columnar():
            return self._columnar_points(False)
        out = []
        if not self.decomps:
            out.append(({}, self.total))
            if self.stage is not None:
                self.stage.bump('noutputs')
            return out
        for keys, weight in self._walk():
            fields = {}
            for name, k in zip(self.decomps, keys):
                if name in self.bucketizers:
                    fields[name] = self.bucketizers[name].bucket_min(k)
                else:
                    fields[name] = k
            out.append((fields, weight))
            if self.stage is not None:
                self.stage.bump('noutputs')
        return out

    def rows(self):
        """Flattened result rows in ordinal form: [key..., weight] per row,
        or a bare total when there are no decompositions (what the
        reference's SkinnerFlattener emits with resultsAsPoints:false)."""
        if self._columnar():
            return self._columnar_points(True)
        if not self.decomps:
            return [self.total]
        rv = []
        for keys, weight in self._walk():
            rv.append(list(keys) + [weight])
        return rv
