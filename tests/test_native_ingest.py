"""Native C++ bulk ingest vs the pure-Python parsers (oracle)."""

import numpy as np
import pytest

from spatialflink_tpu import native
from spatialflink_tpu.streams import bulk, formats
from spatialflink_tpu.utils import IdInterner

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
