"""Invariant linter: the tier-1 gate plus per-rule fixtures.

Three layers:

- **The gate** — the full pass over the REAL tree must be clean (zero
  non-allowlisted findings, zero stale allowlist entries) on every
  tier-1 run; ``doctor --preflight`` runs the same pass.
- **Fixtures** — every rule catches its known-bad snippet and stays
  silent on the known-good twin, so a refactor of the framework cannot
  silently lobotomize a rule.
- **Allowlist lifecycle** — entries suppress exactly what they anchor,
  require a reason, and go STALE (check fails, "remove stale entry")
  the moment their finding disappears: the list only shrinks.

The third-party half of the lint gate (``ruff`` with the committed
``ruff.toml``) runs in the same suite whenever the binary exists; the
analysis framework's built-in bug-class rules (unused-import /
fstring-placeholder / is-literal) cover the overlap when it does not.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from spatialflink_tpu import analysis
from spatialflink_tpu.analysis import (Allowlist, AllowlistError,
                                       check_source, run_analysis)
from spatialflink_tpu.analysis.core import ALLOWLIST_PATH, REPO_ROOT

pytestmark = pytest.mark.analysis


def _ids(findings):
    return [f.rule for f in findings]


def _render(findings):
    return "\n".join(f.render() for f in findings)


@pytest.fixture(scope="module")
def full_report():
    return run_analysis()


# --------------------------------------------------------------------- #
# the tier-1 gate


class TestTreeGate:
    def test_real_tree_is_clean(self, full_report):
        """THE gate: zero non-allowlisted findings across all rules on
        the live tree. A finding here means either fix the code or take
        a reviewed ALLOWLIST.toml entry — never skip this test."""
        assert full_report.ok, (
            f"invariant linter is dirty:\n{_render(full_report.findings)}"
            + "".join(f"\nstale allowlist entry: {e.render()}"
                      for e in full_report.stale))

    def test_every_allowlist_entry_has_a_reason_and_matches(
            self, full_report):
        al = Allowlist.load(ALLOWLIST_PATH)
        assert al.entries, "committed allowlist unexpectedly empty"
        for e in al.entries:
            assert e.reason and len(e.reason) > 10
        # apply() ran inside full_report; nothing stale
        assert not full_report.stale

    def test_all_seven_invariant_rules_registered(self):
        ids = {r.id for r in analysis.all_rules()}
        assert {"jit-coverage", "trace-safety", "host-sync",
                "telemetry-gating", "checkpoint-coverage",
                "thread-shared-state", "recompile-surface"} <= ids
        # the built-in bug-class lints ride along
        assert {"unused-import", "fstring-placeholder",
                "is-literal"} <= ids

    def test_depth_column_documented(self):
        """Every rule carries the depth the docs table renders; the four
        deep rules declare themselves interprocedural."""
        by_id = {r.id: r for r in analysis.all_rules()}
        for rid in ("thread-shared-state", "checkpoint-coverage",
                    "host-sync", "recompile-surface"):
            assert by_id[rid].depth.startswith("interprocedural"), rid
        for rid in ("jit-coverage", "telemetry-gating", "trace-safety",
                    "unused-import"):
            assert by_id[rid].depth == "lexical", rid
        # only cross-MODULE analysis widens the cache key to the tree
        assert by_id["recompile-surface"].interprocedural
        assert not by_id["thread-shared-state"].interprocedural

    def test_scan_covers_the_engine_tree(self, full_report):
        assert full_report.files >= 60  # the whole package, not a subdir


# --------------------------------------------------------------------- #
# per-rule fixtures: known-bad caught, known-good clean


class TestJitCoverageRule:
    BAD = "import jax\n\nkernel = jax.jit(lambda x: x + 1)\n"
    GOOD = ("from spatialflink_tpu.utils.deviceplane import "
            "instrumented_jit\n\n"
            "@instrumented_jit\ndef kernel(x):\n    return x + 1\n")

    def test_bad(self):
        fs = check_source(self.BAD, "spatialflink_tpu/ops/bad.py")
        assert "jit-coverage" in _ids(fs)

    def test_from_import_bad(self):
        fs = check_source("from jax import jit\n",
                          "spatialflink_tpu/parallel/bad.py")
        assert "jit-coverage" in _ids(fs)

    def test_good(self):
        fs = check_source(self.GOOD, "spatialflink_tpu/ops/good.py")
        assert "jit-coverage" not in _ids(fs)

    def test_out_of_scope_module_ignored(self):
        fs = check_source(self.BAD, "spatialflink_tpu/runtime/elsewhere.py")
        assert "jit-coverage" not in _ids(fs)


class TestTraceSafetyRule:
    def _check(self, body):
        src = ("from functools import partial\n"
               "from spatialflink_tpu.utils.deviceplane import "
               "instrumented_jit\n\n" + textwrap.dedent(body))
        return check_source(src, "spatialflink_tpu/ops/k.py")

    def test_control_flow_on_traced_arg(self):
        fs = self._check("""
            @partial(instrumented_jit, static_argnames=("n",))
            def kernel(x, n):
                if x > 0:
                    return x
                return -x
            """)
        assert any(f.rule == "trace-safety" and "control flow" in f.message
                   for f in fs)

    def test_branch_on_static_is_fine(self):
        fs = self._check("""
            @partial(instrumented_jit, static_argnames=("n",))
            def kernel(x, n):
                if n > 4:
                    return x[:4]
                return x
            """)
        assert "trace-safety" not in _ids(fs)

    def test_static_argnums_positional(self):
        fs = self._check("""
            @partial(instrumented_jit, static_argnums=(1,))
            def kernel(x, n):
                if n > 4:
                    return x[:4]
                return x
            """)
        assert "trace-safety" not in _ids(fs)

    def test_int_coercion_of_traced_value(self):
        fs = self._check("""
            @instrumented_jit
            def kernel(x):
                return int(x)
            """)
        assert any(f.rule == "trace-safety" and "concretizes" in f.message
                   for f in fs)

    def test_shape_branch_is_a_warning(self):
        fs = self._check("""
            @instrumented_jit
            def kernel(x):
                if x.shape[0] > 8:
                    return x[:8]
                return x
            """)
        hits = [f for f in fs if f.rule == "trace-safety"]
        assert hits and all(f.severity == "warning" for f in hits)

    def test_iteration_over_traced_arg(self):
        fs = self._check("""
            @instrumented_jit
            def kernel(xs):
                acc = 0.0
                for v in xs:
                    acc = acc + v
                return acc
            """)
        assert any(f.rule == "trace-safety" and "iteration" in f.message
                   for f in fs)

    def test_unhashable_static_default(self):
        fs = self._check("""
            @partial(instrumented_jit, static_argnames=("dims",))
            def kernel(x, dims=[0, 1]):
                return x.sum(dims)
            """)
        assert any(f.rule == "trace-safety" and "unhashable" in f.message
                   for f in fs)

    def test_undecorated_function_untouched(self):
        fs = self._check("""
            def helper(x):
                if x > 0:
                    return int(x)
                return 0
            """)
        assert "trace-safety" not in _ids(fs)


class TestHostSyncRule:
    def test_bare_asarray_on_dispatch_path(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def dispatch(mask):\n    return np.asarray(mask)\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" in _ids(fs)

    def test_block_until_ready_flagged(self):
        fs = check_source(
            "def dispatch(v):\n    return v.block_until_ready()\n",
            "spatialflink_tpu/parallel/x.py")
        assert "host-sync" in _ids(fs)

    def test_item_flagged(self):
        fs = check_source("def f(v):\n    return v.item()\n",
                          "spatialflink_tpu/ops/x.py")
        assert "host-sync" in _ids(fs)

    def test_collect_seam_exempt(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def collect(mask):\n    return np.asarray(mask)\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" not in _ids(fs)

    def test_note_readback_caller_exempt(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def merge(mask, costs):\n"
            "    out = np.asarray(mask)\n"
            "    costs.note_readback('x', out.nbytes)\n"
            "    return out\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" not in _ids(fs)

    def test_host_twin_exempt(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def merge_topk_host(rows):\n    return np.asarray(rows)\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" not in _ids(fs)

    def test_deferred_closure_exempt(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def eval_batch(dev, helper):\n"
            "    def rows(m):\n"
            "        return np.asarray(m).tolist()\n"
            "    return helper._defer_with_stats(dev, None, rows)\n",
            "spatialflink_tpu/operators/base.py")
        assert "host-sync" not in _ids(fs)

    def test_list_literal_construction_exempt(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def build(records):\n"
            "    return np.array([r.x for r in records], np.float64)\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" not in _ids(fs)

    def test_float_of_jax_call_flagged(self):
        fs = check_source(
            "import jax.numpy as jnp\n\n"
            "def dispatch(x):\n    return float(jnp.sum(x))\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" in _ids(fs)

    def test_float_of_config_untouched(self):
        fs = check_source(
            "def f(conf):\n    return float(conf.radius)\n",
            "spatialflink_tpu/ops/x.py")
        assert "host-sync" not in _ids(fs)

    def test_out_of_scope_module(self):
        fs = check_source(
            "import numpy as np\n\n"
            "def f(mask):\n    return np.asarray(mask)\n",
            "spatialflink_tpu/runtime/windows.py")
        assert "host-sync" not in _ids(fs)


class TestTelemetryGatingRule:
    SCOPE = "spatialflink_tpu/streams/x.py"

    def test_ungated_local_session_call(self):
        fs = check_source(
            "from spatialflink_tpu.utils import telemetry as _t\n\n"
            "def drive(stream):\n"
            "    tel = _t.active()\n"
            "    tel.observe('ingest', 1.0)\n", self.SCOPE)
        assert "telemetry-gating" in _ids(fs)

    def test_ungated_self_tel_call(self):
        fs = check_source(
            "class Sink:\n"
            "    def emit(self, w):\n"
            "        self._tel.observe('sink', 1.0)\n", self.SCOPE)
        assert "telemetry-gating" in _ids(fs)

    def test_early_out_gate(self):
        fs = check_source(
            "from spatialflink_tpu.utils import telemetry as _t\n\n"
            "def sweep(starts):\n"
            "    tel = _t.active()\n"
            "    if tel is None or not starts:\n"
            "        return\n"
            "    tel.observe('seal', 1.0)\n", self.SCOPE)
        assert "telemetry-gating" not in _ids(fs)

    def test_enclosing_if_gate(self):
        fs = check_source(
            "class Sink:\n"
            "    def emit(self, w):\n"
            "        if self._tel is not None:\n"
            "            with self._tel.span('sink'):\n"
            "                pass\n", self.SCOPE)
        assert "telemetry-gating" not in _ids(fs)

    def test_ternary_arm_gate(self):
        fs = check_source(
            "import time\n\n"
            "class Sink:\n"
            "    def emit(self, w):\n"
            "        t0 = time.time() if self._tel is not None else 0.0\n"
            "        return t0\n", self.SCOPE)
        assert "telemetry-gating" not in _ids(fs)

    def test_derived_facet_needs_gate(self):
        fs = check_source(
            "from spatialflink_tpu.utils import telemetry as _t\n\n"
            "def drive():\n"
            "    tel = _t.active()\n"
            "    lat = tel.latency if tel is not None else None\n"
            "    lat.note_seal(0, 1.0)\n", self.SCOPE)
        assert "telemetry-gating" in _ids(fs)

    def test_parent_gate_covers_derived_facet(self):
        fs = check_source(
            "from spatialflink_tpu.utils import telemetry as _t\n\n"
            "def drive():\n"
            "    tel = _t.active()\n"
            "    lat = tel.latency if tel is not None else None\n"
            "    if tel is not None:\n"
            "        lat.note_seal(0, 1.0)\n", self.SCOPE)
        assert "telemetry-gating" not in _ids(fs)

    def test_session_parameter_exempt(self):
        fs = check_source(
            "def helper(tel, label):\n"
            "    with tel.span('window', query=label):\n"
            "        pass\n", self.SCOPE)
        assert "telemetry-gating" not in _ids(fs)

    @pytest.mark.parametrize("path", [
        "spatialflink_tpu/operators/join_query.py",
        "spatialflink_tpu/ops/join.py",
        "spatialflink_tpu/driver.py"])
    def test_stage_span_modules_in_scope(self, path):
        """The modules that took a session for the served path's stage
        spans are hot modules too."""
        src = ("from spatialflink_tpu.utils import telemetry as _t\n\n"
               "def pull(it):\n"
               "    tel = _t.active()\n"
               "    with tel.span('window', query='join'):\n"
               "        return next(it)\n")
        assert "telemetry-gating" in _ids(check_source(src, path))

    def test_cold_module_out_of_scope(self):
        fs = check_source(
            "from spatialflink_tpu.utils import telemetry as _t\n\n"
            "def drive():\n"
            "    tel = _t.active()\n"
            "    tel.observe('x', 1.0)\n",
            "spatialflink_tpu/runtime/opserver.py")
        assert "telemetry-gating" not in _ids(fs)


class TestCheckpointCoverageRule:
    BAD = textwrap.dedent("""
        class Assembler:
            def __init__(self):
                self.windows = {}

            def add(self, rec):
                self.windows = dict(self.windows)
                self.watermark = rec.ts
        """)

    def test_mutable_state_without_pair(self):
        fs = check_source(self.BAD, "spatialflink_tpu/runtime/x.py")
        assert "checkpoint-coverage" in _ids(fs)

    def test_pair_present_and_covering_is_clean(self):
        """Since the field-level upgrade the pair must actually COVER the
        state attrs — a snapshot/restore that reads/assigns them all is
        clean (the merely-existing pair is TestFieldCoverage's bad
        fixture in test_analysis_interproc.py)."""
        src = self.BAD + textwrap.dedent("""
            def snapshot(self):
                return {}, {"windows": list(self.windows),
                            "wm": self.watermark}

            def restore(self, state, decode):
                self.windows = dict(state["windows"])
                self.watermark = state["wm"]
            """).replace("\n", "\n    ")
        fs = check_source(src, "spatialflink_tpu/runtime/x.py")
        assert "checkpoint-coverage" not in _ids(fs)

    def test_init_only_state_is_clean(self):
        fs = check_source(
            "class Spec:\n"
            "    def __init__(self):\n"
            "        self.window_ms = 1000\n",
            "spatialflink_tpu/operators/x.py")
        assert "checkpoint-coverage" not in _ids(fs)

    def test_non_state_attrs_ignored(self):
        fs = check_source(
            "class Meter:\n"
            "    def mark(self):\n"
            "        self.count = 1\n",
            "spatialflink_tpu/streams/x.py")
        assert "checkpoint-coverage" not in _ids(fs)


class TestThreadSharedRule:
    def test_unlocked_write_in_lock_owning_class(self):
        fs = check_source(textwrap.dedent("""
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0

                def append(self, ev):
                    self.total += 1
            """), "spatialflink_tpu/utils/x.py")
        assert "thread-shared-state" in _ids(fs)

    def test_locked_write_is_clean(self):
        fs = check_source(textwrap.dedent("""
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0

                def append(self, ev):
                    with self._lock:
                        self.total += 1
            """), "spatialflink_tpu/utils/x.py")
        assert "thread-shared-state" not in _ids(fs)

    def test_caller_locked_suffix_exempt(self):
        fs = check_source(textwrap.dedent("""
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()

                def _bump_locked(self):
                    self.total = 1
            """), "spatialflink_tpu/utils/x.py")
        assert "thread-shared-state" not in _ids(fs)

    def test_documented_class_without_lock(self):
        fs = check_source(
            "class MetricsRegistry:\n"
            "    def __init__(self):\n"
            "        self.counters = {}\n",
            "spatialflink_tpu/utils/x.py")
        assert any(f.rule == "thread-shared-state"
                   and "no instance lock" in f.message for f in fs)

    def test_plain_class_untouched(self):
        fs = check_source(
            "class Plain:\n"
            "    def set(self, v):\n"
            "        self.value = v\n",
            "spatialflink_tpu/utils/x.py")
        assert "thread-shared-state" not in _ids(fs)


class TestBuiltinLintRules:
    def test_unused_import(self):
        fs = check_source("import os\n\nX = 1\n",
                          "spatialflink_tpu/utils/x.py")
        assert "unused-import" in _ids(fs)

    def test_used_import_clean(self):
        fs = check_source("import os\n\nX = os.sep\n",
                          "spatialflink_tpu/utils/x.py")
        assert "unused-import" not in _ids(fs)

    def test_dunder_all_counts_as_use(self):
        fs = check_source(
            "from spatialflink_tpu.utils.metrics import Counter\n\n"
            "__all__ = ['Counter']\n",
            "spatialflink_tpu/utils/x.py")
        assert "unused-import" not in _ids(fs)

    def test_init_py_exempt(self):
        fs = check_source("import os\n",
                          "spatialflink_tpu/utils/__init__.py")
        assert "unused-import" not in _ids(fs)

    def test_future_import_exempt(self):
        fs = check_source("from __future__ import annotations\n\nX = 1\n",
                          "spatialflink_tpu/utils/x.py")
        assert "unused-import" not in _ids(fs)

    def test_fstring_without_placeholder(self):
        fs = check_source('X = f"static text"\n',
                          "spatialflink_tpu/utils/x.py")
        assert "fstring-placeholder" in _ids(fs)

    def test_format_spec_not_flagged(self):
        fs = check_source('def f(v):\n    return f"{v:>11.3f}"\n',
                          "spatialflink_tpu/utils/x.py")
        assert "fstring-placeholder" not in _ids(fs)

    def test_is_literal(self):
        fs = check_source("def f(x):\n    return x is 'control'\n",
                          "spatialflink_tpu/utils/x.py")
        assert "is-literal" in _ids(fs)

    def test_is_none_clean(self):
        fs = check_source("def f(x):\n    return x is None\n",
                          "spatialflink_tpu/utils/x.py")
        assert "is-literal" not in _ids(fs)


# --------------------------------------------------------------------- #
# allowlist lifecycle (the ratchet)


def _fake_tree(tmp_path, source, name="streams/bad.py"):
    pkg = tmp_path / "spatialflink_tpu"
    target = pkg / name
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(source)
    return str(tmp_path)


BAD_TELEMETRY = ("from spatialflink_tpu.utils import telemetry as _t\n\n\n"
                 "def drive(stream):\n"
                 "    tel = _t.active()\n"
                 "    tel.observe('ingest', 1.0)\n")


class TestAllowlistLifecycle:
    def test_entry_suppresses_matching_finding(self, tmp_path):
        root = _fake_tree(tmp_path, BAD_TELEMETRY)
        al = tmp_path / "allow.toml"
        al.write_text(
            '[[allow]]\nrule = "telemetry-gating"\n'
            'path = "spatialflink_tpu/streams/bad.py"\n'
            'symbol = "drive"\n'
            'reason = "fixture: reviewed exception"\n')
        report = run_analysis(root=root, allowlist=str(al))
        assert report.ok
        assert len(report.suppressed) == 1

    def test_stale_entry_fails_check(self, tmp_path):
        """The ratchet: an entry whose finding no longer exists must be
        REMOVED — --check fails and says so."""
        root = _fake_tree(tmp_path, "X = 1\n")  # clean module
        al = tmp_path / "allow.toml"
        al.write_text(
            '[[allow]]\nrule = "telemetry-gating"\n'
            'path = "spatialflink_tpu/streams/bad.py"\n'
            'reason = "fixture: this exception is obsolete"\n')
        report = run_analysis(root=root, allowlist=str(al))
        assert not report.ok and len(report.stale) == 1

        from spatialflink_tpu.analysis.cli import main
        import io

        out = io.StringIO()
        rc = main(["--root", root, "--allowlist", str(al), "--check"],
                  out=out)
        assert rc == 1
        assert "remove stale entry" in out.getvalue()

    def test_stale_only_judged_for_rules_that_ran(self, tmp_path):
        root = _fake_tree(tmp_path, "X = 1\n")
        al = tmp_path / "allow.toml"
        al.write_text(
            '[[allow]]\nrule = "telemetry-gating"\n'
            'path = "spatialflink_tpu/streams/bad.py"\n'
            'reason = "fixture: entry for a rule not in this run"\n')
        report = run_analysis(root=root, rule_ids=["host-sync"],
                              allowlist=str(al))
        assert report.ok  # the entry's rule did not run -> not stale

    def test_reason_is_mandatory(self, tmp_path):
        al = tmp_path / "allow.toml"
        al.write_text('[[allow]]\nrule = "host-sync"\n'
                      'path = "spatialflink_tpu/ops/x.py"\n')
        with pytest.raises(AllowlistError, match="reason"):
            Allowlist.load(str(al))

    def test_unknown_keys_rejected(self, tmp_path):
        al = tmp_path / "allow.toml"
        al.write_text('[[allow]]\nrule = "host-sync"\n'
                      'path = "spatialflink_tpu/ops/x.py"\n'
                      'reason = "r"\nexpires = "never"\n')
        with pytest.raises(AllowlistError, match="unknown key"):
            Allowlist.load(str(al))

    def test_symbol_anchor_matches_nested_scopes(self, tmp_path):
        root = _fake_tree(
            tmp_path,
            "from spatialflink_tpu.utils import telemetry as _t\n\n\n"
            "def drive(stream):\n"
            "    def inner():\n"
            "        tel = _t.active()\n"
            "        tel.observe('x', 1.0)\n"
            "    return inner\n")
        al = tmp_path / "allow.toml"
        al.write_text(
            '[[allow]]\nrule = "telemetry-gating"\n'
            'path = "spatialflink_tpu/streams/bad.py"\n'
            'symbol = "drive"\n'
            'reason = "fixture: anchor covers nested scopes"\n')
        report = run_analysis(root=root, allowlist=str(al))
        assert report.ok and len(report.suppressed) == 1


# --------------------------------------------------------------------- #
# CLI contract


class TestCli:
    def _run(self, *args):
        from spatialflink_tpu.analysis.cli import main
        import io

        out = io.StringIO()
        rc = main(list(args), out=out)
        return rc, out.getvalue()

    def test_check_passes_on_real_tree(self):
        rc, out = self._run("--check")
        assert rc == 0 and "check: PASS" in out

    def test_json_schema(self):
        rc, out = self._run("--format", "json")
        doc = json.loads(out)
        assert rc == 0 and doc["ok"] is True
        assert set(doc) >= {"ok", "files", "rules", "findings",
                            "allowlisted", "stale_allowlist_entries"}
        assert doc["files"] >= 60
        for row in doc["allowlisted"]:
            assert row["reason"]

    def test_rule_filter_and_list(self):
        rc, out = self._run("--rule", "jit-coverage", "--format", "json")
        assert rc == 0 and json.loads(out)["rules"] == ["jit-coverage"]
        rc, out = self._run("--list-rules")
        assert rc == 0 and "telemetry-gating" in out

    def test_unknown_rule_exits_2(self):
        rc, _ = self._run("--rule", "no-such-rule")
        assert rc == 2

    def test_injected_bad_snippet_fails_check(self, tmp_path):
        """The acceptance bar: drop one known-bad file into a tree and
        --check exits 1."""
        root = _fake_tree(tmp_path, BAD_TELEMETRY)
        rc, out = self._run("--root", root, "--allowlist", "none",
                            "--check")
        assert rc == 1 and "telemetry-gating" in out

    def test_module_entrypoint_subprocess(self):
        """One end-to-end spawn of `python -m spatialflink_tpu.analysis`
        — the exact command the README documents and doctor tells a
        dirty-preflight operator to run."""
        proc = subprocess.run(
            [sys.executable, "-m", "spatialflink_tpu.analysis",
             "--check", "--format", "json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert json.loads(proc.stdout)["ok"] is True


# --------------------------------------------------------------------- #
# doctor --preflight integration


class TestPreflightIntegration:
    def test_preflight_runs_the_pass(self, capsys):
        from spatialflink_tpu import doctor

        rc = doctor.preflight(require_backend="cpu", as_json=True)
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0, doc
        names = {c["check"]: c for c in doc["checks"]}
        assert "static_analysis" in names
        assert names["static_analysis"]["ok"] is True
        assert doc["analysis"]["ok"] is True
        assert doc["analysis"]["findings"] == 0
        assert doc["analysis"]["files"] >= 60
        # per-rule finding counts, not one opaque total: every ran rule
        # reports (zero, on a clean tree)
        by_rule = doc["analysis"]["findings_by_rule"]
        assert set(by_rule) >= set(doc["analysis"]["rules"])
        assert all(n == 0 for n in by_rule.values())
        assert doc["analysis"]["stale_pragmas"] == 0

    def test_preflight_fails_on_dirty_tree(self, tmp_path, monkeypatch,
                                           capsys):
        """A dirty tree fails preflight the same way a CPU fallback
        does."""
        from spatialflink_tpu import doctor
        from spatialflink_tpu.analysis import core as _core

        root = _fake_tree(tmp_path, BAD_TELEMETRY)
        orig = _core.run_analysis
        monkeypatch.setattr(
            "spatialflink_tpu.analysis.run_analysis",
            lambda **kw: orig(root=root, allowlist=None))
        rc = doctor.preflight(require_backend="cpu", as_json=True)
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        names = {c["check"]: c for c in doc["checks"]}
        assert names["static_analysis"]["ok"] is False
        assert doc["analysis"]["findings"] >= 1


# --------------------------------------------------------------------- #
# third-party lint gate (ruff) — rides the same suite when installed


class TestRuffGate:
    def test_ruff_clean_when_available(self):
        ruff = shutil.which("ruff")
        if ruff is None:
            pytest.skip("ruff not installed in this container; the "
                        "built-in bug-class rules cover the overlap")
        proc = subprocess.run(
            [ruff, "check", "--no-cache", "spatialflink_tpu"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_ruff_config_is_committed_and_bug_scoped(self):
        cfg = os.path.join(REPO_ROOT, "ruff.toml")
        assert os.path.exists(cfg)
        text = open(cfg).read()
        assert "F821" in text and "F401" in text
        # no style families — the config stays a bug gate
        for family in ('"E', '"W', '"C9', '"N8'):
            assert family not in text
