"""Tenant accounting plane tests: per-dispatch attribution conservation
(attributed kernel-ms sums to the measured span by construction, padded
slots excluded), the skewed-fleet cost-vs-count separation, quota
admission (429 ``quota-exceeded`` distinct from ``shed``, slot release,
snapshot round-trip), the ``/tenants`` + ``/tenants/<id>`` +
``/fleet/tenants`` endpoint schemas incl. 404/405, ``tenant="T"``
Prometheus labels, the digest block, ``doctor tenants``, the satellite
trace-eviction and run_id/snapshot_seq surfaces, ledger-off hot-path
silence and window-table identity, and the ``--kafka-follow --chaos``
acceptance run fetching ``/tenants`` mid-run."""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import yaml

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (PointPointRangeQuery,
                                        QueryConfiguration, QueryType)
from spatialflink_tpu.runtime.opserver import OpServer, active_server
from spatialflink_tpu.runtime.queryplane import (QueryRegistry, QuerySpec,
                                                 QuerySpecError)
from spatialflink_tpu.streams import reset_memory_brokers, resolve_broker
from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.utils import metrics as _metrics
from spatialflink_tpu.utils import telemetry as _telemetry
from spatialflink_tpu.utils.accounting import (DEFAULT_TENANT, ROW_FIELDS,
                                               QuotaExceeded, TenantLedger,
                                               gini, merge_tenant_payloads,
                                               parse_tenant_quotas)
from spatialflink_tpu.utils.metrics import scoped_registry
from spatialflink_tpu.utils.telemetry import (WindowTraceBook,
                                              prometheus_text,
                                              status_snapshot,
                                              telemetry_session)

pytestmark = pytest.mark.accounting

CONF = "conf/spatialflink-conf.yml"
IN1 = "points.geojson"
GRID = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
CONTROL = json.dumps({"geometry": {"type": "control", "coordinates": []}})
QPTS = [(116.5, 40.3), (116.0, 40.0), (117.0, 40.9)]


def _recs(n=3000, seed=0, dt_ms=20):
    rng = np.random.default_rng(seed)
    t0 = 1_700_000_000_000
    return [Point.create(float(115.5 + rng.random() * 2),
                         float(39.6 + rng.random() * 1.5), GRID,
                         obj_id=f"v{i % 13}", timestamp=int(t0 + i * dt_ms))
            for i in range(n)]


def _conf(**kw):
    kw.setdefault("window_size_ms", 10_000)
    kw.setdefault("slide_ms", 5_000)
    return QueryConfiguration(QueryType.WindowBased, **kw)


def _reg(specs, family="range", radius=0.5, k=None, **reg_kw):
    reg = QueryRegistry(family, radius=radius, k=k, **reg_kw)
    for s in specs:
        reg.admit(s)
    reg.apply()
    return reg


def _fed(ledger, tenant_weights, kernel_s=0.004, records=100,
         nbytes=4096, label="op", start=1_700_000_000_000):
    """One dispatch parked + resolved across the given tenants."""
    ledger.note_dispatch(label, start, kernel_s, records, nbytes)
    ledger.resolve(label, start,
                   [(f"q-{t}", t, w) for t, w in tenant_weights])


class TestQuotaParse:
    def test_parse_forms(self):
        q = parse_tenant_quotas("acme:4,kernel_ms_s=250;free:1")
        assert q == {"acme": {"max_active": 4, "kernel_ms_s": 250.0},
                     "free": {"max_active": 1}}
        assert parse_tenant_quotas("") == {}
        assert parse_tenant_quotas(" t : 2 ") == {"t": {"max_active": 2}}

    def test_parse_errors_name_the_part(self):
        for bad, frag in [("acme", "T:max_active"),
                          ("acme:many", "int"),
                          ("acme:-1", ">= 0"),
                          ("acme:1,wat=3", "kernel_ms_s"),
                          ("acme:1,kernel_ms_s=zero", "number"),
                          ("acme:1,kernel_ms_s=0", "> 0"),
                          ("acme:1;acme:2", "duplicate")]:
            with pytest.raises(ValueError, match=frag):
                parse_tenant_quotas(bad)


class TestGini:
    def test_gini_bounds(self):
        assert gini([]) == 0.0
        assert gini([5.0, 5.0, 5.0, 5.0]) == pytest.approx(0.0)
        # one tenant holds everything that matters
        assert gini([1000.0, 1.0, 1.0, 1.0]) > 0.7
        # zero/negative values are ignored, not counted as poorest
        assert gini([3.0, 0.0, -1.0]) == pytest.approx(0.0)


class TestLedgerAttribution:
    def test_conservation_is_exact_per_dispatch(self):
        led = TenantLedger()
        span_ms = 3.1718281828
        led.note_dispatch("op", 1000, span_ms / 1e3, 450, 1 << 20)
        led.resolve("op", 1000, [("a", "acme", 3.0), ("b", "free", 1.0),
                                 ("c", "free", 0.0)])
        rows = led.to_dict()["tenants"]
        # rows are display-rounded to 1e-3; the residual counter proves
        # the internal split was exact
        total = sum(r["kernel_ms"] for r in rows.values())
        assert total == pytest.approx(span_ms, abs=2e-3)
        assert rows["acme"]["kernel_ms"] == pytest.approx(
            span_ms * 0.75, abs=1e-3)
        assert led.max_residual_ms < 1e-9
        assert sum(r["records_in"] for r in rows.values()) == 450
        assert sum(r["bytes_moved"] for r in rows.values()) == 1 << 20

    def test_zero_total_weight_splits_uniformly(self):
        led = TenantLedger()
        _fed(led, [("a", 0.0), ("b", 0.0)], kernel_s=0.002)
        rows = led.to_dict()["tenants"]
        assert rows["a"]["kernel_ms"] == pytest.approx(1.0, abs=1e-3)
        assert rows["b"]["kernel_ms"] == pytest.approx(1.0, abs=1e-3)

    def test_empty_slots_credit_the_default_tenant(self):
        led = TenantLedger(default_tenant="house")
        led.note_dispatch("op", 7, 0.001, 10, 64)
        led.resolve("op", 7, [])
        assert led.to_dict()["tenants"]["house"]["kernel_ms"] == \
            pytest.approx(1.0, abs=1e-3)

    def test_late_resolve_is_counted_not_crashed(self):
        led = TenantLedger()
        led.resolve("op", 99, [("a", "t", 1.0)])
        d = led.to_dict()
        assert d["late_resolves"] == 1 and d["n"] == 0

    def test_stale_pending_ages_into_default(self):
        led = TenantLedger(default_tenant="house", pending_max_age_s=0.0)
        led.note_dispatch("static", 1, 0.002, 5, 32)
        led.tick()
        d = led.to_dict()
        assert d["flushed"] == 1 and d["pending"] == 0
        assert d["tenants"]["house"]["kernel_ms"] == pytest.approx(
            2.0, abs=1e-3)

    def test_pending_capacity_flushes_oldest(self):
        led = TenantLedger(default_tenant="house", pending_capacity=2)
        for w in range(3):
            led.note_dispatch("op", w, 0.001, 1, 8)
        assert led.to_dict()["pending"] == 2 and led.flushed == 1
        # the flushed span (window 0) landed on the default tenant
        led.resolve("op", 0, [("a", "t", 1.0)])
        assert led.late_resolves == 1

    def test_redispatch_same_window_merges_spans(self):
        led = TenantLedger()
        led.note_dispatch("op", 5, 0.001, 10, 100)
        led.note_dispatch("op", 5, 0.002, 20, 200)
        led.resolve("op", 5, [("a", "t", 1.0)])
        row = led.to_dict()["tenants"]["t"]
        assert row["kernel_ms"] == pytest.approx(3.0, abs=1e-3)
        assert row["records_in"] == 30 and row["bytes_moved"] == 300

    def test_rate_sees_recent_attribution(self):
        led = TenantLedger()
        _fed(led, [("acme", 1.0)], kernel_s=0.5)
        assert led.kernel_ms_rate("acme") > 0.0
        assert led.kernel_ms_rate("ghost") == 0.0

    def test_payload_schema_and_series_bucket(self):
        led = TenantLedger(series_capacity=4)
        _fed(led, [("acme", 2.0), ("free", 1.0)])
        led.tick()
        doc = led.payload()
        assert doc["schema"] == "tenants-v1" and doc["n"] == 2
        assert set(ROW_FIELDS) <= set(doc["tenants"]["acme"])
        assert doc["fairness"]["top"] == "acme"
        assert doc["series"] and "kernel_ms" in doc["series"][-1]
        one = led.tenant_payload("acme")
        assert one["schema"] == "tenant-v1" and one["query_ids"] == \
            ["q-acme"]
        assert led.tenant_payload("ghost") is None

    def test_snapshot_restore_round_trip(self):
        led = TenantLedger(default_tenant="house")
        _fed(led, [("acme", 3.0), ("free", 1.0)])
        led.note_window("acme", "q-acme", 7)
        led.note_quota_rejection("free")
        snap = json.loads(json.dumps(led.snapshot()))  # JSON-safe
        led2 = TenantLedger()
        led2.restore(snap)
        assert led2.to_dict()["tenants"] == led.to_dict()["tenants"]
        assert led2.default_tenant == "house"
        # restored cumulative counters are the delta base, not fresh load
        assert led2.kernel_ms_rate("acme") == pytest.approx(0.0, abs=1e-9)

    def test_merge_tenant_payloads_sums_and_refairs(self):
        a = TenantLedger()
        _fed(a, [("acme", 1.0)], kernel_s=0.009)
        b = TenantLedger()
        _fed(b, [("acme", 1.0), ("free", 3.0)], kernel_s=0.004)
        merged = merge_tenant_payloads([a.payload(), b.payload(), None])
        assert merged["schema"] == "fleet-tenants-v1"
        assert merged["workers"] == 2 and merged["n"] == 2
        assert merged["tenants"]["acme"]["kernel_ms"] == pytest.approx(
            9.0 + 1.0, abs=1e-3)
        assert merged["fairness"]["top"] == "acme"
        assert merged["dispatches"] == 2 and merged["resolved"] == 2


class TestSpecTenant:
    def test_default_tenant_and_roundtrip(self):
        s = QuerySpec.from_dict({"id": "a", "x": 1, "y": 2},
                                default_family="range",
                                default_tenant="acme")
        assert s.tenant == "acme" and s.to_dict()["tenant"] == "acme"
        d = QuerySpec.from_dict({"id": "a", "x": 1, "y": 2},
                                default_family="range")
        assert d.tenant == DEFAULT_TENANT
        assert "tenant" not in d.to_dict()  # default stays implicit

    def test_tenant_validation(self):
        for bad in ("", 5, "x" * 129):
            with pytest.raises(QuerySpecError, match="tenant"):
                QuerySpec.from_dict(
                    {"id": "a", "x": 1, "y": 2, "tenant": bad},
                    default_family="range")


class TestQuotaAdmission:
    def test_max_active_blocks_then_releases(self):
        with scoped_registry() as counters:
            reg = QueryRegistry(
                "range", radius=0.5,
                tenant_quotas={"acme": {"max_active": 1}})
            reg.admit({"id": "a", "x": 1, "y": 2, "tenant": "acme"})
            with pytest.raises(QuotaExceeded, match="max_active"):
                reg.admit({"id": "b", "x": 1, "y": 2, "tenant": "acme"})
            assert counters.counter("queries-quota-rejected").count == 1
            # other tenants and updates of the held query are unaffected
            reg.admit({"id": "c", "x": 1, "y": 2, "tenant": "free"})
            reg.admit({"id": "a", "x": 3, "y": 3, "tenant": "acme"})
            # a quota rejection never created an entry
            assert "b" not in {e["id"] for e in
                               reg.status()["queries"]}
            # releasing the slot admits the next one
            reg.retire("a")
            reg.apply()
            reg.admit({"id": "b", "x": 1, "y": 2, "tenant": "acme"})

    def test_rate_quota_uses_the_ledger(self):
        with scoped_registry(), telemetry_session() as tel:
            _fed(tel.tenants, [("acme", 1.0)], kernel_s=5.0)
            reg = QueryRegistry(
                "range", radius=0.5,
                tenant_quotas={"acme": {"max_active": 99,
                                        "kernel_ms_s": 0.001}})
            with pytest.raises(QuotaExceeded, match="kernel_ms_s"):
                reg.admit({"id": "a", "x": 1, "y": 2, "tenant": "acme"})
            assert tel.tenants.to_dict()["tenants"]["acme"][
                "quota_rejections"] == 1

    def test_quota_state_rides_registry_snapshot(self):
        reg = QueryRegistry("range", radius=0.5, default_tenant="house",
                            tenant_quotas={"acme": {"max_active": 2}})
        reg.admit({"id": "a", "x": 1, "y": 2})
        reg.apply()
        snap = json.loads(json.dumps(reg.snapshot()))
        reg2 = QueryRegistry("range", radius=0.5)
        reg2.restore(snap)
        assert reg2.default_tenant == "house"
        assert reg2.tenant_quotas == {"acme": {"max_active": 2}}
        assert reg2.active_entries()[0].spec.tenant == "house"
        st = reg2.status()
        assert st["default_tenant"] == "house"
        assert st["tenant_quotas"]["acme"]["max_active"] == 2

    def test_shed_is_not_quota(self):
        """The two 429 causes stay distinct: shed parks an entry, quota
        refuses without one — and both count on the tenant's row."""
        with scoped_registry(), telemetry_session() as tel:
            reg = QueryRegistry("range", radius=0.5)
            reg.shedding = True
            e = reg.admit({"id": "a", "x": 1, "y": 2, "tenant": "acme"})
            assert e.state.value == "shed"
            assert tel.tenants.to_dict()["tenants"]["acme"]["shed"] == 1


class TestDispatchAttribution:
    def _specs(self, tenants):
        return [{"id": f"q{i}", "x": x, "y": y, "tenant": t}
                for i, ((x, y), t) in enumerate(zip(QPTS, tenants))]

    def test_dynamic_fleet_conserves_and_excludes_padding(self):
        recs = _recs(2500)
        with scoped_registry(), telemetry_session() as tel:
            reg = _reg(self._specs(["acme", "acme", "free"]))
            out = list(PointPointRangeQuery(_conf(), GRID).run_dynamic(
                iter(recs), reg, 0.5))
            ten = tel.tenants.to_dict()
        assert out and ten["resolved"] > 0
        # every dispatch the demux saw was resolved, none left parked
        assert ten["pending"] == 0 and ten["late_resolves"] == 0
        # 3 live in a bucket of 4: the padded slot never shows up as a
        # tenant, and nothing aged into the default catch-all
        assert set(ten["tenants"]) == {"acme", "free"}
        assert ten["flushed"] == 0
        # conservation: attributed kernel-ms sums to the measured spans
        # CostProfiles recorded at the same site (exact by construction);
        # read unrounded, since the payloads round each row to 3 decimals
        total_measured = float(tel.costs._cost_ms.sum())
        total_attributed = sum(r["kernel_ms"]
                               for r in tel.tenants._tenants.values())
        assert total_attributed == pytest.approx(total_measured, rel=1e-6)
        assert ten["max_residual_ms"] < 1e-6

    def test_skewed_fleet_hot_tenant_pays_for_its_work(self):
        """Two tenants, one query each: 'hot' sits in the record cluster,
        'cold' in an empty corner. Cost attribution must follow candidate
        WORK, not slot count — the hot tenant's attributed share exceeds
        its 50% share of the fleet by a wide margin."""
        rng = np.random.default_rng(3)
        t0 = 1_700_000_000_000
        recs = [Point.create(float(116.5 + rng.random() * 0.05),
                             float(40.3 + rng.random() * 0.05), GRID,
                             obj_id=f"v{i}", timestamp=int(t0 + i * 20))
                for i in range(2500)]
        with scoped_registry(), telemetry_session() as tel:
            reg = _reg([{"id": "hot", "x": 116.5, "y": 40.3,
                         "tenant": "acme"},
                        {"id": "cold", "x": 117.5, "y": 41.0,
                         "tenant": "free"}])
            list(PointPointRangeQuery(_conf(), GRID).run_dynamic(
                iter(recs), reg, 0.2))
            rows = tel.tenants.to_dict()["tenants"]
        total = sum(r["kernel_ms"] for r in rows.values())
        assert total > 0
        assert rows["acme"]["kernel_ms"] / total > 0.9
        assert rows["free"]["kernel_ms"] / total < 0.1

    def test_window_tables_identical_ledger_on_vs_off(self):
        recs = _recs(2000)

        def tables(session):
            with scoped_registry():
                reg = _reg(self._specs(["acme", "acme", "free"]))
                if session:
                    with telemetry_session():
                        out = list(PointPointRangeQuery(
                            _conf(), GRID).run_dynamic(iter(recs), reg,
                                                       0.5))
                else:
                    out = list(PointPointRangeQuery(
                        _conf(), GRID).run_dynamic(iter(recs), reg, 0.5))
            return [(w.window_start, w.window_end,
                     tuple(w.extras["query_ids"]),
                     tuple(tuple(r.obj_id for r in q)
                           for q in w.records)) for w in out]

        assert tables(session=True) == tables(session=False)

    def test_ledger_silent_without_session(self, monkeypatch):
        """Hot-path contract: an uninstrumented dynamic run never touches
        the ledger — same zero-call spy discipline as the other planes."""
        calls = {"n": 0}
        for name in ("note_dispatch", "resolve", "note_window",
                     "maybe_tick"):
            orig = getattr(TenantLedger, name)

            def spy(self, *a, _orig=orig, **k):
                calls["n"] += 1
                return _orig(self, *a, **k)

            monkeypatch.setattr(TenantLedger, name, spy)
        with scoped_registry():
            reg = _reg(self._specs(["acme", "acme", "free"]))
            assert _telemetry.active() is None
            list(PointPointRangeQuery(_conf(), GRID).run_dynamic(
                iter(_recs(1200)), reg, 0.5))
        assert calls["n"] == 0

    def test_zero_recompiles_with_ledger_on(self):
        """The ledger is host-side arithmetic on already-materialized
        masks: turning it on must not add a single XLA compile."""
        from spatialflink_tpu.ops.range import range_filter_point_multi_masks

        recs = _recs(1500)
        with scoped_registry():
            list(PointPointRangeQuery(_conf(), GRID).run_dynamic(
                iter(recs), _reg(self._specs(["a", "a", "b"])), 0.5))
        before = range_filter_point_multi_masks._cache_size()
        with scoped_registry(), telemetry_session():
            list(PointPointRangeQuery(_conf(), GRID).run_dynamic(
                iter(recs), _reg(self._specs(["a", "a", "b"])), 0.5))
        assert range_filter_point_multi_masks._cache_size() == before, \
            "enabling the tenant ledger recompiled the multi kernel"


class TestServing:
    def _get(self, url, expect_json=True):
        with urllib.request.urlopen(url, timeout=5) as r:
            body = r.read()
            return r.status, (json.loads(body) if expect_json
                              else body.decode())

    def test_endpoints_schema_404_405(self):
        with telemetry_session() as tel:
            _fed(tel.tenants, [("acme", 3.0), ("free", 1.0)])
            srv = OpServer(port=0).start()
            try:
                code, doc = self._get(srv.url + "/tenants")
                assert code == 200 and doc["schema"] == "tenants-v1"
                assert set(doc["tenants"]) == {"acme", "free"}
                assert doc["fairness"]["top"] == "acme"
                code, one = self._get(srv.url + "/tenants/acme")
                assert code == 200 and one["schema"] == "tenant-v1"
                assert one["kernel_ms"] == pytest.approx(3.0, abs=1e-3)
                with pytest.raises(urllib.error.HTTPError) as ei:
                    self._get(srv.url + "/tenants/ghost")
                assert ei.value.code == 404
                # wrong method: 405 with the Allow header
                req = urllib.request.Request(
                    srv.url + "/tenants", data=b"{}", method="POST")
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=5)
                assert ei.value.code == 405
                assert "GET" in ei.value.headers.get("Allow", "")
                # not a supervisor: /fleet/tenants explains itself
                code, fed = self._get(srv.url + "/fleet/tenants")
                assert code == 200 and "note" in fed and fed["n"] == 0
            finally:
                srv.close()

    def test_no_session_note_fallbacks(self):
        srv = OpServer()
        assert _telemetry.active() is None
        doc = srv.tenants_payload()
        assert doc["tenants"] == {} and "note" in doc
        code, err = srv.tenant_payload("acme")
        assert code == 404 and "telemetry session" in err["error"]

    def test_quota_429_distinct_from_shed_on_post(self):
        reg = QueryRegistry(
            "range", radius=0.5,
            tenant_quotas={"acme": {"max_active": 1}}).install()
        try:
            srv = OpServer()
            code, _ = srv.admit_query_payload(
                {"id": "a", "x": 1, "y": 2, "tenant": "acme"})
            assert code == 200
            code, doc = srv.admit_query_payload(
                {"id": "b", "x": 1, "y": 2, "tenant": "acme"})
            assert code == 429 and doc["error"].startswith(
                "quota-exceeded")
            assert doc["tenant"] == "acme"
            # governor shedding keeps its own 429 wording and DOES park
            reg.shedding = True
            code, doc = srv.admit_query_payload(
                {"id": "c", "x": 1, "y": 2, "tenant": "free"})
            assert code == 429 and "admission shed" in doc["error"]
            assert doc["query"]["state"] == "shed"
        finally:
            reg.uninstall()

    def test_prometheus_tenant_labels(self):
        with telemetry_session() as tel:
            _fed(tel.tenants, [("acme", 3.0), ("free", 1.0)])
            tel.tenants.note_quota_rejection("free")
            text = prometheus_text(tel)
        assert 'spatialflink_tenant_kernel_ms_total{tenant="acme"}' in text
        assert 'spatialflink_tenant_kernel_ms_total{tenant="free"}' in text
        assert ('spatialflink_tenant_quota_rejections_total'
                '{tenant="free"} 1') in text
        assert "spatialflink_tenant_fairness_gini" in text

    def test_status_digest_and_stderr_line(self):
        from spatialflink_tpu.runtime.opserver import format_digest

        with telemetry_session() as tel:
            _fed(tel.tenants, [("acme", 9.0), ("free", 1.0)])
            tel.tenants.note_quota_rejection("free")
            snap = status_snapshot(tel)
        ten = snap["status"]["tenants"]
        assert ten["n"] == 2 and ten["top"] == "acme"
        assert ten["quota_rejections"] == 1
        line = format_digest(snap)
        assert "tenant top acme 90%" in line and "quota-rej 1" in line

    def test_doctor_tenants_renders_the_ledger(self, tmp_path):
        from spatialflink_tpu import doctor
        from spatialflink_tpu.utils.deviceplane import BUNDLE_SCHEMA

        led = TenantLedger()
        _fed(led, [("acme", 3.0), ("free", 1.0)])
        bundle = tmp_path / "bundle-x"
        bundle.mkdir()
        (bundle / "manifest.json").write_text(json.dumps(
            {"schema": BUNDLE_SCHEMA, "reason": "test", "ts_ms": 1,
             "files": ["tenants.json"]}))
        (bundle / "tenants.json").write_text(json.dumps(led.payload()))
        buf = io.StringIO()
        assert doctor.tenants(str(bundle), out=buf) == 0
        text = buf.getvalue()
        assert "acme" in text and "fairness" in text and "residual" in text
        buf = io.StringIO()
        assert doctor.tenants(str(bundle), as_json=True, out=buf) == 0
        doc = json.loads(buf.getvalue())
        assert doc["tenants"]["acme"]["kernel_ms"] == pytest.approx(
            3.0, abs=1e-3)
        assert doctor.main(["tenants", str(bundle)]) == 0


class TestSatellites:
    def test_trace_ring_overflow_is_visible(self):
        """Satellite: eviction by the capacity ring counts — on the book,
        the counter, and the /trace/recent payload."""
        import types

        with scoped_registry() as counters:
            book = WindowTraceBook(capacity=2)
            for w in range(5):
                book.note("q", w, "kernel", 0.0, 0.001)
            assert book.total == 5 and book.evicted == 3
            assert counters.counter("trace-evictions").count == 3
            srv = OpServer(telemetry=types.SimpleNamespace(traces=book))
            doc = srv.traces_payload()
            assert doc["evicted"] == 3 and doc["latest_seq"] == 5
            assert len(doc["traces"]) == 2
        # and the no-book fallback still carries the fields
        assert OpServer().traces_payload()["evicted"] == 0

    def test_status_snapshot_stamps_run_id_and_seq(self):
        s1 = status_snapshot()
        s2 = status_snapshot()
        assert s1["run_id"] == s2["run_id"]
        assert len(s1["run_id"]) == 12
        int(s1["run_id"], 16)  # hex
        assert s2["snapshot_seq"] > s1["snapshot_seq"] > 0

    def test_fleet_monitor_drops_stale_polls(self, tmp_path):
        from spatialflink_tpu.runtime.fleetsup import FleetMonitor

        mon = FleetMonitor(str(tmp_path), 1)

        def poll(run_id, seq):
            mon.ingest_poll(0, {"run_id": run_id, "snapshot_seq": seq,
                                "status": {"records_in": seq}},
                            None, alive=True, incarnation=0)

        poll("r1", 1)
        poll("r1", 3)
        poll("r1", 2)  # raced an older snapshot in: dropped
        assert mon.stale_polls == 1
        assert [s["records_in"] for s in mon._series[0]] == [1, 3]
        # a restarted worker's fresh run_id resets the high-water mark
        poll("r2", 1)
        assert mon.stale_polls == 1
        assert [s["records_in"] for s in mon._series[0]] == [1, 3, 1]
        # pre-satellite workers (no run_id) are never dropped
        mon.ingest_poll(0, {"status": {"records_in": 9}}, None,
                        alive=True, incarnation=0)
        assert len(mon._series[0]) == 4


class TestFollowAcceptance:
    """The ISSUE acceptance run: ``--kafka-follow --chaos --status-port
    0`` with two tenants; ``GET /tenants`` mid-run shows both with
    conserved attribution; each query's routed window table is identical
    to a dedicated ledger-off run."""

    @pytest.fixture(autouse=True)
    def _fresh_brokers(self):
        reset_memory_brokers()
        yield
        reset_memory_brokers()

    def test_follow_chaos_tenants_mid_run(self, tmp_path):
        from spatialflink_tpu.driver import main

        with open(CONF) as f:
            d = yaml.safe_load(f)
        d["kafkaBootStrapServers"] = "memory://acct-follow"
        d["query"]["radius"] = 0.5
        d["query"]["thresholds"]["outOfOrderTuples"] = 0
        d["window"].update(interval=2, step=1)
        cfg = tmp_path / "c.yml"
        cfg.write_text(yaml.safe_dump(d))
        route_a = tmp_path / "qa.jsonl"
        route_b = tmp_path / "qb.jsonl"
        qfile = tmp_path / "q.json"
        qfile.write_text(json.dumps([
            {"id": "qa", "x": 116.5, "y": 40.5, "tenant": "acme",
             "route": f"file:{route_a}"},
            {"id": "qb", "x": 116.0, "y": 40.0, "tenant": "free",
             "route": f"file:{route_b}"}]))
        broker = resolve_broker("memory://acct-follow")
        recs = []

        def produce():
            t0 = int(time.time() * 1000)
            for i in range(350):
                p = Point.create(116.4 + 0.002 * (i % 60), 40.5, GRID,
                                 obj_id=f"veh{i % 7}",
                                 timestamp=t0 + i * 40)
                recs.append(p)
                broker.produce(IN1, serialize_spatial(p, "GeoJSON"))
                time.sleep(0.004)
            broker.produce(IN1, CONTROL)

        ops = {}

        def fetch_mid_run():
            deadline = time.monotonic() + 25
            srv = None
            while time.monotonic() < deadline and srv is None:
                srv = active_server()
                if srv is None or srv.port is None:
                    srv = None
                    time.sleep(0.005)
            if srv is None:
                ops["error"] = "no server"
                return
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(srv.url + "/tenants",
                                                timeout=3) as r:
                        doc = json.loads(r.read())
                except OSError:
                    time.sleep(0.05)
                    continue
                if doc.get("resolved", 0) >= 3 and \
                        set(doc.get("tenants") or {}) >= {"acme", "free"}:
                    ops["tenants"] = doc
                    return
                time.sleep(0.05)
            ops["error"] = "tenant rows never materialized"

        prod = threading.Thread(target=produce, daemon=True)
        plane = threading.Thread(target=fetch_mid_run, daemon=True)
        with scoped_registry():
            prod.start()
            plane.start()
            rc = main(["--config", str(cfg), "--kafka", "--kafka-follow",
                       "--option", "1", "--status-port", "0",
                       "--queries-file", str(qfile), "--live-stats",
                       "--telemetry-interval", "0.3",
                       "--chaos", "seed=7,fetch_fail=0.2,latency=0.2,"
                                  "latency_ms=4",
                       "--retry", "attempts=12,base_ms=1,max_ms=20"])
            prod.join(timeout=30)
            plane.join(timeout=30)
        assert rc == 0
        assert "error" not in ops, ops
        doc = ops["tenants"]
        assert doc["schema"] == "tenants-v1"
        assert doc["max_residual_ms"] < 1e-6
        assert doc["late_resolves"] == 0
        assert all(doc["tenants"][t]["kernel_ms"] >= 0
                   for t in ("acme", "free"))
        # identity vs the LEDGER-OFF truth: each routed table equals a
        # dedicated static run with no telemetry session at all
        conf = QueryConfiguration(QueryType.WindowBased, 2_000, 1_000)
        for route, (x, y) in [(route_a, (116.5, 40.5)),
                              (route_b, (116.0, 40.0))]:
            got = {tuple(doc["window"]): doc["records"] for doc in
                   map(json.loads, route.read_text().splitlines())}
            assert got, route
            ded = {}
            assert _telemetry.active() is None
            for w in PointPointRangeQuery(conf, GRID).run(
                    iter(list(recs)), Point.create(x, y, GRID), 0.5):
                ded[(w.window_start, w.window_end)] = [
                    serialize_spatial(r, "GeoJSON") for r in w.records]
            for win, docs in got.items():
                assert docs == ded.get(win, []), (route, win)
