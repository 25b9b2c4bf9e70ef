"""The decode interner's hash -> id index (``IdInterner.lookup_hashes`` /
``index_hashes``) against the per-unique-hash string loop it replaced:
identical ``obj_id`` arrays and interner order over multi-chunk streams of
every native format, and the ``intern-index-*`` counters."""

import numpy as np
import pytest

from spatialflink_tpu import native
from spatialflink_tpu.streams import bulk
from spatialflink_tpu.utils import IdInterner
from spatialflink_tpu.utils.metrics import scoped_registry

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")

T0 = 1_700_000_000_000


def _reference_intern_hashes(data, oid_hash, oid_start, oid_len, interner,
                             normalize):
    """The loop ``_intern_hashes`` ran before the hash index, verbatim."""
    uniq, first, inv = np.unique(oid_hash, return_index=True, return_inverse=True)
    ids = np.empty(uniq.shape[0], np.int32)
    for u, j in enumerate(first):
        s = data[oid_start[j]: oid_start[j] + oid_len[j]].decode("utf-8", "replace")
        ids[u] = interner.intern(normalize(s))
    return ids[inv]


def _csv(ids, t0=0):
    return [f"{o},{T0 + t0 + i},{116 + (i % 97) * 1e-3},{40 + (i % 89) * 1e-3}"
            for i, o in enumerate(ids)]


def _geojson(ids):
    return ['{"geometry": {"type": "Point", "coordinates": [%r, %r]}, '
            '"properties": {"oID": "%s", "timestamp": %d}}'
            % (1.0 + i % 13, 2.0 + i % 7, o, T0 + i) for i, o in enumerate(ids)]


def _wkt(ids):
    out = []
    for i, o in enumerate(ids):
        x, y = 1.0 + i % 5, 2.0 + i % 3
        if i % 4 == 0:
            out.append(f"{o}, {T0 + i}, LINESTRING ({x} {y}, {x + 1} {y + 1})")
        elif i % 9 == 0:  # native-rejected: re-parsed in Python
            out.append(f"{o}, {T0 + i}, MULTIPOLYGON ((({x} {y}, {x + 1} {y}, "
                       f"{x + 1} {y + 1}, {x} {y})))")
        else:
            out.append(f"{o}, {T0 + i}, POLYGON (({x} {y}, {x + 1} {y}, "
                       f"{x + 1} {y + 1}, {x} {y}))")
    return out


def _geojson_geoms(ids):
    return ['{"geometry": {"type": "Polygon", "coordinates": '
            '[[[%d, 0], [%d, 0], [%d, 1], [%d, 0]]]}, '
            '"properties": {"oID": "%s", "timestamp": %d}}'
            % (i % 5, i % 5 + 1, i % 5 + 1, i % 5, o, T0 + i)
            for i, o in enumerate(ids)]


def _chunks(lines, sizes):
    out, i, k = [], 0, 0
    while i < len(lines):
        n = sizes[k % len(sizes)]
        out.append(lines[i:i + n])
        i, k = i + n, k + 1
    return out


def _fleet(n, fleet, prefix="taxi"):
    return [f"{prefix}{i % fleet}" for i in range(n)]


def _with_rejects(lines):
    # every 5th record carries an ISO date: the native CSV parser rejects
    # it and _merge_rejects interns the re-parsed id directly
    return [ln if i % 5 else ln.replace(f",{T0 + i},", ",2024-01-15 12:30:00,", 1)
            for i, ln in enumerate(lines)]


rng = np.random.default_rng(7)

STREAMS = {
    # ids repeating across chunks (a fleet reporting round-robin)
    "fleet_repeats": (bulk.bulk_parse_csv, _csv(_fleet(3000, 257)), [200]),
    # new ids arriving mid-stream, in shuffled order within chunks
    "new_ids_mid_stream": (
        bulk.bulk_parse_csv,
        _csv(_fleet(600, 40) + [f"late{j}" for j in rng.permutation(500)]
             + _fleet(600, 60), t0=0),
        [128, 77, 300]),
    # quotes and whitespace that normalize to one id, first seen in
    # different spellings in different chunks
    "csv_quotes_whitespace": (
        bulk.bulk_parse_csv,
        _csv([('"%s"' % o, " %s " % o, '" %s"' % o, o)[i % 4]
              for i, o in enumerate(_fleet(800, 37, "car"))]),
        [50, 13]),
    # rejected lines: ids first interned by the re-parse path, then met
    # again by the native path (and the other way round)
    "csv_rejects": (bulk.bulk_parse_csv,
                    _with_rejects(_csv(_fleet(900, 71, "r"))), [64, 250]),
    # invalid UTF-8 in ids decodes with replacement characters
    "csv_invalid_utf8": (
        bulk.bulk_parse_csv,
        [ln.encode() for ln in _csv(_fleet(300, 11, "b"))]
        + [b"bad\xe2\x82," + str(T0).encode() + b",1.0,2.0",
           b"bad\xff," + str(T0).encode() + b",1.0,2.0",
           b"\xc3\xa9t\xc3," + str(T0).encode() + b",1.0,2.0"] * 7,
        [29]),
    "geojson_points": (bulk.bulk_parse_geojson, _geojson(_fleet(1200, 150)),
                       [100, 64]),
    "wkt_geoms": (bulk.bulk_parse_wkt, _wkt(_fleet(700, 45, "g")), [60, 31]),
    "geojson_geoms": (bulk.bulk_parse_geojson_geoms,
                      _geojson_geoms(_fleet(500, 33, "z")), [40]),
    # an all-miss stream of ever-new ids (many run merges)
    "all_miss": (bulk.bulk_parse_csv, _csv([f"u{i}" for i in range(20000)]),
                 [2048, 512, 3000]),
}


def _run(parse, chunks, restore_at=None):
    interner = IdInterner()
    oids = []
    for k, chunk in enumerate(chunks):
        if k == restore_at:
            # a checkpoint restore mid-stream: same ids, an empty index
            interner = IdInterner.from_list(interner.to_list())
        data = b"\n".join(c if isinstance(c, bytes) else c.encode()
                          for c in chunk)
        oids.append(parse(data, interner=interner).obj_id.copy())
    return oids, interner.to_list()


@pytest.mark.parametrize("restore", [False, True], ids=["live", "restored"])
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_index_matches_reference_loop(name, restore, monkeypatch):
    parse, lines, sizes = STREAMS[name]
    chunks = _chunks(lines, sizes)
    restore_at = len(chunks) // 2 if restore else None
    got, got_ids = _run(parse, chunks, restore_at)
    monkeypatch.setattr(bulk, "_intern_hashes", _reference_intern_hashes)
    want, want_ids = _run(parse, chunks, restore_at)
    assert got_ids == want_ids
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_index_lookup_and_runs():
    """Random inserts: every indexed hash resolves to its id, nothing else
    resolves, and runs stay geometric (each at most half the one before)."""
    r = np.random.default_rng(3)
    it = IdInterner()
    keys = np.unique(r.integers(0, 2**63, 50_000, dtype=np.uint64))
    vals = r.permutation(keys.shape[0]).astype(np.int32)
    order = r.permutation(keys.shape[0])
    lo = 0
    for step in [1, 7, 300, 2048, 5, 9000]:
        part = np.sort(order[lo:lo + step])
        it.index_hashes(keys[part], vals[part])
        lo += step
        sizes = [k.shape[0] for k, _ in it._runs]
        assert all(a > 2 * b for a, b in zip(sizes, sizes[1:])), sizes
        assert all((np.diff(k.astype(np.float64)) > 0).all()
                   for k, _ in it._runs)
    seen = np.sort(order[:lo])
    q = np.concatenate([keys, keys[seen[:100]]])
    ids, miss = it.lookup_hashes(q)
    known = np.zeros(keys.shape[0], bool)
    known[seen] = True
    expect_hit = np.concatenate([known, np.ones(100, bool)])
    np.testing.assert_array_equal(ids[expect_hit],
                                  np.concatenate([vals, vals[seen[:100]]])[expect_hit])
    assert (ids[~expect_hit] == -1).all()
    np.testing.assert_array_equal(miss, np.flatnonzero(~expect_hit))


@pytest.mark.parametrize("chunk", [64, 250])
def test_index_counters_count_fleet_misses_once(chunk):
    """A fleet of F ids over N records (each id met once in its first
    chunk): F misses, N - F hits, and nothing left from another test."""
    fleet, n = 300, 4000
    lines = _csv(_fleet(n, fleet))
    with scoped_registry() as reg:
        interner = IdInterner()
        for c in _chunks(lines, [chunk]):
            bulk.bulk_parse_csv("\n".join(c).encode(), interner=interner)
        snap = reg.snapshot()
    assert snap["intern-index-misses"] == fleet
    assert snap["intern-index-hits"] == n - fleet
    with scoped_registry() as reg:
        assert "intern-index-hits" not in reg.snapshot()
