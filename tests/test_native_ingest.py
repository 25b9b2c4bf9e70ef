"""Native C++ bulk ingest vs the pure-Python parsers (oracle)."""

import ctypes
import os
import sys

import numpy as np
import pytest

from spatialflink_tpu import native
from spatialflink_tpu.streams import bulk, formats
from spatialflink_tpu.utils import IdInterner
from spatialflink_tpu.utils.metrics import scoped_registry

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def _oracle(lines, fmt, **kw):
    pts = [formats.parse_spatial(ln, fmt, None, **kw) for ln in lines]
    interner = IdInterner()
    return (
        np.array([p.x for p in pts]),
        np.array([p.y for p in pts]),
        np.array([p.timestamp for p in pts], np.int64),
        [p.obj_id for p in pts],
    )


def _check(parsed, lines, fmt, **kw):
    ox, oy, ots, ooid = _oracle(lines, fmt, **kw)
    np.testing.assert_allclose(parsed.x, ox, rtol=1e-12)
    np.testing.assert_allclose(parsed.y, oy, rtol=1e-12)
    np.testing.assert_array_equal(parsed.ts, ots)
    got_ids = [parsed.interner.lookup(int(i)) for i in parsed.obj_id]
    assert got_ids == ooid


class TestCsv:
    def test_plain(self):
        lines = [f"obj{i % 7},{1700000000000 + i * 10},{116 + i * 0.001},{40 + i * 0.002}"
                 for i in range(500)]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode())
        assert len(parsed) == 500
        _check(parsed, lines, "csv")

    def test_quotes_spaces_blank_lines(self):
        lines = ['"a1" , 123 , 1.5 , 2.5', "a2,456,3.25,4.75"]
        data = ("\n\n" + "\n".join(lines) + "\n\n").encode()
        parsed = bulk.bulk_parse_csv(data)
        assert len(parsed) == 2
        _check(parsed, lines, "csv")

    def test_tsv_and_schema_permutation(self):
        # schema [oID, ts, x, y] column indices permuted
        lines = ["7.5\t1.25\tcar9\t1700000005000", "8.5\t2.25\tcar10\t1700000006000"]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode(), delimiter="\t",
                                     schema=(2, 3, 0, 1))
        _check(parsed, lines, "tsv", schema=(2, 3, 0, 1))

    def test_iso_dates_fall_back(self):
        lines = ["t1,2024-01-15 12:30:00,1.0,2.0",
                 "t2,1700000000000,3.0,4.0",
                 "t3,2024-01-15 12:31:00,5.0,6.0"]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode())
        _check(parsed, lines, "csv")
        assert parsed.ts[0] > 1_600_000_000_000  # the ISO line really parsed

    def test_no_oid_no_ts(self):
        lines = ["1.0,2.0", "3.0,4.0"]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode(),
                                     schema=(None, None, 0, 1))
        _check(parsed, lines, "csv", schema=(None, None, 0, 1))

    def test_python_fallback_matches(self, monkeypatch):
        lines = ["a,1,2.0,3.0", "b,2,4.0,5.0"]
        data = "\n".join(lines).encode()
        native_parsed = bulk.bulk_parse_csv(data)
        monkeypatch.setenv("SPATIALFLINK_NATIVE", "0")
        py_parsed = bulk.bulk_parse_csv(data)
        np.testing.assert_array_equal(native_parsed.x, py_parsed.x)
        np.testing.assert_array_equal(native_parsed.ts, py_parsed.ts)
        assert ([native_parsed.interner.lookup(int(i)) for i in native_parsed.obj_id]
                == [py_parsed.interner.lookup(int(i)) for i in py_parsed.obj_id])


class TestGeoJson:
    def _line(self, oid, ts, x, y):
        return ('{"geometry": {"type": "Point", "coordinates": [%s, %s]}, '
                '"properties": {"oID": %s, "timestamp": %s}}' % (x, y, oid, ts))

    def test_plain(self):
        lines = [self._line(f'"v{i % 5}"', 1700000000000 + i, 116 + i * 0.01, 40 + i * 0.01)
                 for i in range(200)]
        parsed = bulk.bulk_parse_geojson("\n".join(lines).encode())
        assert len(parsed) == 200
        _check(parsed, lines, "geojson", date_format=None)

    def test_numeric_and_null_oid(self):
        lines = [self._line("42", 100, 1.0, 2.0), self._line("null", 200, 3.0, 4.0)]
        parsed = bulk.bulk_parse_geojson("\n".join(lines).encode())
        _check(parsed, lines, "geojson", date_format=None)

    def test_nonpoint_raises_clear_error(self):
        poly = ('{"geometry": {"type": "Polygon", "coordinates": '
                '[[[0,0],[1,0],[1,1],[0,0]]]}, "properties": {"oID": "p1", '
                '"timestamp": 5}}')
        lines = [self._line('"a"', 1, 1.0, 2.0), poly]
        with pytest.raises(ValueError, match="non-Point"):
            bulk.bulk_parse_geojson("\n".join(lines).encode())

    def test_kafka_envelope_scoping(self):
        # envelope-level broker "timestamp" must NOT shadow the properties one
        inner = self._line('"env1"', 4242, 7.5, 8.5)
        lines = ['{"topic": "t", "timestamp": 1699000000001, "value": %s}' % inner]
        parsed = bulk.bulk_parse_geojson("\n".join(lines).encode())
        assert parsed.ts[0] == 4242
        assert parsed.interner.lookup(int(parsed.obj_id[0])) == "env1"
        _check(parsed, lines, "geojson", date_format=None)

    def test_coordinates_key_in_properties_not_confused(self):
        ln = ('{"properties": {"coordinates": "fake", "oID": "c1", "timestamp": 9},'
              ' "geometry": {"type": "Point", "coordinates": [5.0, 6.0]}}')
        parsed = bulk.bulk_parse_geojson(ln.encode())
        assert parsed.x[0] == 5.0 and parsed.y[0] == 6.0 and parsed.ts[0] == 9
        _check(parsed, [ln], "geojson", date_format=None)

    def test_bool_oid_falls_back(self):
        lines = [self._line("true", 100, 1.0, 2.0)]
        parsed = bulk.bulk_parse_geojson("\n".join(lines).encode())
        _check(parsed, lines, "geojson", date_format=None)  # str(True) == "True"

    def test_csv_quoted_padded_oid(self):
        lines = ['" a1 ",123,1.5,2.5', "a1,456,3.0,4.0"]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode())
        _check(parsed, lines, "csv")
        # both normalize to the same object id
        assert parsed.obj_id[0] == parsed.obj_id[1]

    def test_quoted_int_timestamp(self):
        lines = [self._line('"q"', '"1700000000123"', 9.0, 8.0)]
        parsed = bulk.bulk_parse_geojson("\n".join(lines).encode())
        assert parsed.ts[0] == 1700000000123


class TestBatchEnd2End:
    def test_to_batch(self):
        from spatialflink_tpu.index import UniformGrid

        g = UniformGrid(0.0, 10.0, 0.0, 10.0, num_grid_partitions=10)
        lines = [f"o{i},{1000 + i},{i % 10}.5,{(i * 3) % 10}.5" for i in range(100)]
        parsed = bulk.bulk_parse_csv("\n".join(lines).encode())
        batch = parsed.to_batch(g)
        assert int(batch.valid.sum()) == 100
        assert (np.asarray(batch.cell)[np.asarray(batch.valid)] >= 0).all()


def test_library_is_keyed_on_source_contents(tmp_path, monkeypatch):
    """The loaded binary is the one built from the committed ingest.cpp: its
    path carries the source's hash, so an edited source (or a binary copied
    in from elsewhere) never loads by mtime alone."""
    import hashlib

    assert native.lib()._name == native._so_path()
    src = tmp_path / "ingest.cpp"
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    src.write_text("int a;")
    before = native._so_path()
    src.write_text("int b;")
    after = native._so_path()
    assert before != after
    assert after.endswith(hashlib.sha256(b"int b;").hexdigest()[:16] + ".so")


# ------------------------------------------------- exact number conversion
#
# The native CSV parser converts short plain decimals itself and leaves every
# other coordinate shape to strtod; timestamps are read in one pass. Each
# case below checks the native x, y and ts bit for bit against Python's
# float() and int() of the same fields, and the rejected line indices
# against the parser's contract.

_INT64_MAX = 2**63 - 1


def _native_csv(lines, delimiter=b","):
    """The native parser's own outputs, before any Python re-parse:
    (x, y, ts, rejected line indices, (fast, slow) field counts)."""
    data = "\n".join(lines).encode()
    cap = len(lines) + 1
    xs, ys = np.empty(cap, np.float64), np.empty(cap, np.float64)
    ts, oh = np.empty(cap, np.int64), np.empty(cap, np.uint64)
    os_, ol = np.empty(cap, np.int64), np.empty(cap, np.int32)
    rej = np.empty(cap, np.int64)
    nrej, nfloat = ctypes.c_long(0), (ctypes.c_long * 2)()
    n = native.lib().sf_parse_points_csv(
        data + b"\0", len(data), delimiter, 0, 1, 2, 3,
        bulk._ptr(xs, ctypes.c_double), bulk._ptr(ys, ctypes.c_double),
        bulk._ptr(ts, ctypes.c_int64), bulk._ptr(oh, ctypes.c_uint64),
        bulk._ptr(os_, ctypes.c_int64), bulk._ptr(ol, ctypes.c_int32),
        bulk._ptr(rej, ctypes.c_int64), ctypes.byref(nrej), nfloat)
    return xs[:n], ys[:n], ts[:n], rej[: nrej.value].tolist(), tuple(nfloat)


def _assert_float_bits(got, fields):
    want = np.array([float(f.strip('"')) for f in fields], np.float64)
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint64),
                                  want[~nan].view(np.uint64))


def _decimals(n_digits, rng, count=400):
    """``count`` decimals of ``n_digits`` random digits (the first not 0)
    padded with up to two zeros on either side, the point at every position
    in turn (none, leading, inside, trailing), with every sign."""
    out = []
    for i in range(count):
        d = str(rng.integers(1, 10)) + "".join(
            rng.choice(list("0123456789"), n_digits - 1))
        d = "0" * int(rng.integers(0, 3)) + d + "0" * int(rng.integers(0, 3))
        pt = i % (len(d) + 2) - 1  # -1: no point
        s = d if pt < 0 else d[:pt] + "." + d[pt:]
        out.append(("", "-", "+")[i % 3] + s)
    return out


@pytest.mark.parametrize("n_digits", range(1, 18))
def test_coordinate_bits_match_python_float(n_digits):
    rng = np.random.default_rng(1000 + n_digits)
    xs = _decimals(n_digits, rng)
    ys = _decimals(n_digits, rng)
    lines = [f"o{i},{1700000000000 + i},{x},{y}"
             for i, (x, y) in enumerate(zip(xs, ys))]
    gx, gy, gts, rej, _ = _native_csv(lines)
    assert rej == []
    _assert_float_bits(gx, xs)
    _assert_float_bits(gy, ys)
    np.testing.assert_array_equal(gts, 1700000000000 + np.arange(len(lines)))


_EDGE_FIELDS = [
    "0", "-0", "+0", "-0.0", "0.000", "000", "-000.000", ".5", "5.", "-.5",
    "+5.", "007.2500", "115.9492041", "-40.4216613", "1.10000000000000000000",
    "123456789012345", "1234567890123456", "0.000000000000001",
    "999999999999999.9", "0.1234567890123456789012",    # 22 fraction digits
    "0.12345678901234567890123",                         # 23 fraction digits
    "1.0000000000000000000001", "0.0000000000000000000001",
    "0.00000000000000000000001",
    "1e5", "1.5E-3", "-2e+10", "1e308", "1e-320", "1e400", "4.9e-324",
    "inf", "-inf", "+inf", "Infinity", "nan", "-nan", "NaN",
    " 1.5", "1.5 ", "\t-2.25\t", '"1.5"', '" 1.5 "', '"-0"',
    "9007199254740993", "2.2250738585072011e-308", "0.1", "0.3",
    # 16-17 digits, where (double)m / 10^f would round twice and miss
    "9706.036993534523", "903270576755.9419", "98423904.288728971",
    "0.52554523491122058", "0.0000044277280168630491",
]


@pytest.mark.parametrize("field", _EDGE_FIELDS)
def test_edge_coordinate_bits_match_python_float(field):
    lines = [f"a,1,{field},{field}", f"b,2,1.5,{field}", f"c,3,{field},-2.5"]
    gx, gy, _, rej, _ = _native_csv(lines)
    assert rej == []
    _assert_float_bits(gx, [field, "1.5", field])
    _assert_float_bits(gy, [field, field, "-2.5"])


@pytest.mark.parametrize("field", [
    "", "1.2.3", "--1", "+-1", "1e", "1e+", ".", "+", "-", "abc", "1_0",
    "0x", "1.5x", "1 5", "5-", "in", "-.e1",
])
def test_malformed_coordinates_are_rejected_as_before(field):
    """strtod decides every field the short-decimal path declines: these
    lines go to Python, in x or in y, and the good lines around them stay."""
    lines = ["a,1,1.5,2.5", f"b,2,{field},2.5", "c,3,1.5,2.5",
             f"d,4,1.5,{field}", "e,5,3.5,4.5"]
    gx, gy, gts, rej, _ = _native_csv(lines)
    assert rej == [1, 3]
    np.testing.assert_array_equal(gts, [1, 3, 5])
    _assert_float_bits(gx, ["1.5", "1.5", "3.5"])


@pytest.mark.parametrize("delimiter, line", [
    (b"5", "a515354"), (b".", "a.1.3.4"), (b"e", "ae1e3e4"),
    (b"E", "aE1E3E4"), (b"x", "ax1x0x4"),
])
def test_delimiter_that_continues_a_number_is_rejected_as_before(
        delimiter, line):
    """strtod reads on past a field when the delimiter after it continues
    the number (``3`` then ``54``, ``.4``, ``e4``; ``0`` then ``x4``), and
    such a line has always gone to Python: the short-decimal path must not
    take the field."""
    _, _, _, rej, _ = _native_csv([line, line], delimiter)
    assert rej == [0, 1]


@pytest.mark.parametrize("n_digits", range(1, 21))
def test_timestamp_matches_python_int(n_digits):
    """Digits-only timestamps of every length; past int64 the value
    saturates as strtoll does."""
    rng = np.random.default_rng(2000 + n_digits)
    fields = ["".join(rng.choice(list("0123456789"), n_digits))
              for _ in range(200)]
    fields += ["9" * n_digits, "0" * n_digits, "1" + "0" * (n_digits - 1)]
    lines = [f"o,{t},1.5,2.5" for t in fields]
    _, _, gts, rej, _ = _native_csv(lines)
    assert rej == []
    assert gts.tolist() == [min(int(t), _INT64_MAX) for t in fields]


@pytest.mark.parametrize("field", [
    "2024-01-15 12:30:00", "-5", "+5", "1.0", "1e3", "12a", "",
    "9223372036854775808x",
])
def test_non_digit_timestamps_go_to_python(field):
    lines = ["a,1,1.5,2.5", f"b,{field},1.5,2.5", "c,3,1.5,2.5"]
    _, _, gts, rej, _ = _native_csv(lines)
    assert rej == [1]
    assert gts.tolist() == [1, 3]


def test_float_counters_on_tdrive_rows():
    """The benchmark's own T-Drive rows take the short-decimal path for
    every x and y: two fast fields a record, none to strtod."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    from perfbench import stream

    conf = {"grid_bbox": [115.5, 39.6, 117.6, 41.1], "num_grid_cells": 100}
    s = stream.make_stream(conf, 3000, 2**31 + 7, "taxis", "t", 500, 0.2)
    s.ts = stream.T0 + np.arange(len(s)) * 10
    data = s.rows(0, len(s)).tobytes()
    with scoped_registry() as reg:
        parsed = bulk.bulk_parse_csv(data[:-1])
        snap = reg.snapshot()
    assert snap["csv-float-fast"] == 2 * len(s)
    assert snap["csv-float-slow"] == 0
    np.testing.assert_array_equal(parsed.x, s.x)
    np.testing.assert_array_equal(parsed.y, s.y)


@pytest.mark.parametrize("x, y, slow", [
    ("116.12345678901234", "40.5", 1),      # 17 significant digits
    ("1.1641e2", "4.05e1", 2),               # exponents
    ("116.5", "40.5", 0),
])
def test_float_counters_count_strtod_fields(x, y, slow):
    with scoped_registry() as reg:
        parsed = bulk.bulk_parse_csv(f"o,1,{x},{y}\no,2,{x},{y}".encode())
        snap = reg.snapshot()
    assert snap["csv-float-slow"] == 2 * slow
    assert snap["csv-float-fast"] == 2 * (2 - slow)
    _assert_float_bits(parsed.x, [x, x])
    _assert_float_bits(parsed.y, [y, y])
