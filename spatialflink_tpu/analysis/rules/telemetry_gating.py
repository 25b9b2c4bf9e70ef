"""Rule 4 — hot-path telemetry gating: no session touch without a
``tel is None``-style gate.

The telemetry contract since PR 2: with no active session the record
loop is byte-identical — zero span/observe/record calls. The runtime
hot-path spy proves that for the paths the tests drive; this rule proves
the *shape* of the guarantee everywhere in the hot modules
(``streams/*``, ``runtime/windows.py``, ``operators/base.py``, the join's
``operators/join_query.py`` and ``ops/join.py``, ``driver.py``): every
method call on a session object — a value bound from
``telemetry.active()`` or read from a ``self._tel``-style cached field —
must be dominated by a None-gate (enclosing ``if tel is not None:``
branch, matching ternary arm, or an earlier ``if tel is None:
return/continue`` early-out).

Values *passed in* as parameters are exempt: the once-per-stream gate
happens where ``active()`` is called, and helpers below it receive a
proven-non-None session.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, Optional

from spatialflink_tpu.analysis.core import (Finding, ModuleSource, Rule,
                                            register)
from spatialflink_tpu.analysis.rules.common import dotted, is_none_guarded

#: attribute names that cache a session on an instance.
_SESSION_ATTRS = {"_tel", "tel"}
#: session facets that are themselves Optional (opt-in planes): names
#: bound from ``tel.latency``/``tel.costs``/``tel.traces``/``tel.tenants``
#: inherit the gating obligation.
_DERIVED_ATTRS = {"latency", "costs", "traces", "tenants"}


def _is_active_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) \
        and isinstance(node.func, ast.Attribute) \
        and node.func.attr == "active" and not node.args \
        and not node.keywords


def _session_names(fn: ast.AST) -> Dict[str, Optional[str]]:
    """Session-valued local names in ``fn`` → the parent session name
    they derive from (None for a directly-bound session).

    Recognized bindings: ``tel = *.active()``, ``tel = self._tel``, and
    the derived facets ``lat = tel.latency`` / ``lat = tel.latency if
    tel is not None else None``. A derived name is None exactly when its
    parent is, so a gate on either name dominates the use."""
    out: Dict[str, Optional[str]] = {}
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        name = node.targets[0].id
        value = node.value
        if _is_active_call(value):
            out[name] = None
            continue
        src = dotted(value)
        if src is not None and src.startswith("self.") \
                and src.split(".")[-1] in _SESSION_ATTRS:
            out[name] = None
            continue
        # `lat = tel.latency if tel is not None else None` — the ternary
        # body carries the derivation, the orelse pins None
        if isinstance(value, ast.IfExp) \
                and isinstance(value.orelse, ast.Constant) \
                and value.orelse.value is None:
            value = value.body
            src = dotted(value)
        if src is not None and "." in src:
            root, attr = src.split(".")[0], src.split(".")[-1]
            if attr in _DERIVED_ATTRS and (
                    root in out or (src.startswith("self.")
                                    and src.split(".")[1]
                                    in _SESSION_ATTRS)):
                out[name] = root if root in out else \
                    ".".join(src.split(".")[:2])
    return out


@register
class TelemetryGatingRule(Rule):
    id = "telemetry-gating"
    contract = ("every session-object call in hot modules is dominated by "
                "a `tel is None` gate — the no-session record loop stays "
                "byte-identical")
    runtime_twin = ("hot-path spy tests (test_telemetry / test_deviceplane "
                    "/ test_latencyplane zero-call assertions)")
    severity = "error"
    scope = ("spatialflink_tpu/streams/*.py",
             "spatialflink_tpu/runtime/windows.py",
             "spatialflink_tpu/operators/base.py",
             "spatialflink_tpu/operators/join_query.py",
             "spatialflink_tpu/ops/join.py",
             "spatialflink_tpu/driver.py",
             "spatialflink_tpu/utils/accounting.py")

    def check(self, mod: ModuleSource,
              project=None) -> Iterator[Finding]:
        session_names: Dict[ast.AST, Dict[str, Optional[str]]] = {
            fn: _session_names(fn) for fn in ast.walk(mod.tree)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda))}
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            roots = self._session_roots(mod, node, session_names)
            if roots is None:
                continue
            if roots and any(is_none_guarded(mod, node, var)
                             for var in roots):
                continue
            chain = dotted(node.func) or f"…().{node.func.attr}"
            yield self.finding(
                mod, node,
                f"session call {chain}() is not dominated by a None-gate "
                "— without a session this line must be unreachable "
                "(`if tel is None`-style gate, once per stream)")

    def _session_roots(self, mod: ModuleSource, call: ast.Call,
                       session_names) -> Optional[list]:
        """The variable names whose non-None proof would gate this call
        (the rooted name plus, for derived facets, the parent session);
        [] for a direct ``active().x()`` chain (never gateable); None
        when the call does not touch a session."""
        chain = dotted(call.func)
        if chain is None:
            inner = call.func
            while isinstance(inner, ast.Attribute):
                inner = inner.value
            return [] if _is_active_call(inner) else None
        parts = chain.split(".")
        if len(parts) >= 3 and parts[0] == "self" \
                and parts[1] in _SESSION_ATTRS:
            return [f"{parts[0]}.{parts[1]}"]
        if len(parts) >= 2:
            for fn in mod.enclosing_functions(call):
                bindings = session_names.get(fn, {})
                if parts[0] in bindings:
                    roots = [parts[0]]
                    parent = bindings[parts[0]]
                    while parent is not None:
                        roots.append(parent)
                        parent = bindings.get(parent)
                    return roots
        return None
