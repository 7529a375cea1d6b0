"""Telemetry schema checker — `scripts/check_telemetry_schema.py` migrated into dolo-lint.

Coverage is identical to the original script (which remains as a thin shim over this
module): every literal telemetry call site in ``dolomite_engine_tpu/`` must use a name
declared in the `utils/telemetry.py` tables, record literals must carry their kind's
required fields, and — in reverse — every declared name must have a call site (no schema
rot). See that script's docstring for the full call-site grammar.

Rules: ``telemetry-undeclared-name``, ``telemetry-missing-field``,
``telemetry-dead-declaration``.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from ..framework import Checker, Finding, SourceFile

# the modules allowed to call the registry through `self` / `self.telemetry`
_SELF_CALL_FILES = ("telemetry.py", "diagnostics.py")


@dataclass
class Usage:
    counters: set[str] = field(default_factory=set)
    events: set[str] = field(default_factory=set)
    gauges: set[str] = field(default_factory=set)
    kinds: set[str] = field(default_factory=set)

    def update(self, other: "Usage") -> None:
        self.counters |= other.counters
        self.events |= other.events
        self.gauges |= other.gauges
        self.kinds |= other.kinds


def load_tables() -> dict:
    from dolomite_engine_tpu.utils.telemetry import (
        KNOWN_COUNTERS,
        KNOWN_EVENTS,
        KNOWN_GAUGES,
        RECORD_SCHEMA,
    )

    return {
        "counters": KNOWN_COUNTERS,
        "events": KNOWN_EVENTS,
        "gauges": KNOWN_GAUGES,
        "records": RECORD_SCHEMA,
    }


def _is_telemetry_receiver(call: ast.Call, filename: str) -> bool:
    receiver = call.func.value  # type: ignore[union-attr]
    try:
        text = ast.unparse(receiver)
    except Exception:
        return False
    if "telemetry" in text.lower():
        return True
    return text == "self" and os.path.basename(filename) in _SELF_CALL_FILES


def _literal_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def scan_tree(tree: ast.AST, filename: str, tables: dict) -> tuple[list[tuple[int, str]], Usage]:
    """Scan one parsed file. Returns ([(line, message)], usage). Message text matches the
    original scripts/check_telemetry_schema.py wording exactly."""
    errors: list[tuple[int, str]] = []
    usage = Usage()
    counters, events = tables["counters"], tables["events"]
    gauges, records = tables["gauges"], tables["records"]

    for node in ast.walk(tree):
        # {"kind": "x", ...} literals — the internal _emit payloads
        if isinstance(node, ast.Dict):
            keys = [_literal_str(k) for k in node.keys if k is not None]
            if "kind" not in keys:
                continue
            kind = _literal_str(node.values[keys.index("kind")])
            if kind is None:
                continue
            usage.kinds.add(kind)
            if kind not in records:
                errors.append(
                    (node.lineno, f"record kind '{kind}' not declared in RECORD_SCHEMA")
                )
                continue
            literal_keys = {k for k in keys if k}
            missing = [f for f in records[kind] if f not in literal_keys]
            # payloads assembled incrementally (record.update / **fields) only carry some
            # keys literally; require the declared fields only when the literal looks
            # complete (heuristic: more literal keys than just "kind")
            if missing and len(literal_keys) > 1:
                errors.append(
                    (
                        node.lineno,
                        f"record kind '{kind}' literal is missing required field(s) {missing}",
                    )
                )
            continue

        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
            continue
        method = node.func.attr
        if method not in ("count", "event", "event_once", "gauge", "emit_record"):
            continue
        if not _is_telemetry_receiver(node, filename):
            continue
        name = _literal_str(node.args[0]) if node.args else None
        if name is None:
            continue  # dynamic name (e.g. count()'s internal event fan-out)

        if method == "count":
            usage.counters.add(name)
            if name not in counters:
                errors.append((node.lineno, f"counter '{name}' not in KNOWN_COUNTERS"))
            wants_event = any(
                kw.arg == "event"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True
                for kw in node.keywords
            )
            if wants_event:
                usage.events.add(name)
                if name not in events:
                    errors.append(
                        (
                            node.lineno,
                            f"counter '{name}' emits an event (event=True) but is not in "
                            "KNOWN_EVENTS",
                        )
                    )
        elif method in ("event", "event_once"):
            usage.events.add(name)
            if name not in events:
                errors.append((node.lineno, f"event '{name}' not in KNOWN_EVENTS"))
        elif method == "gauge":
            usage.gauges.add(name)
            if name not in gauges:
                errors.append((node.lineno, f"gauge '{name}' not in KNOWN_GAUGES"))
        elif method == "emit_record":
            usage.kinds.add(name)
            if name not in records:
                errors.append(
                    (node.lineno, f"record kind '{name}' not declared in RECORD_SCHEMA")
                )
            elif not any(isinstance(a, ast.keyword) and a.arg is None for a in node.keywords):
                # no **fields forwarding: the literal keywords must cover the schema
                literal_kw = {kw.arg for kw in node.keywords if kw.arg} | {"step"}
                missing = [f for f in records[name] if f not in literal_kw]
                if missing:
                    errors.append(
                        (
                            node.lineno,
                            f"emit_record('{name}') is missing required field(s) {missing}",
                        )
                    )
    return errors, usage


def reverse_errors(tables: dict, usage: Usage) -> list[str]:
    """A declared name nobody writes is dead weight / schema rot."""
    errors: list[str] = []
    for name in tables["counters"]:
        if name not in usage.counters:
            errors.append(f"KNOWN_COUNTERS entry '{name}' has no call site in the package")
    for name in tables["events"]:
        if name not in usage.events:
            errors.append(f"KNOWN_EVENTS entry '{name}' has no call site in the package")
    for name in tables["gauges"]:
        if name not in usage.gauges:
            errors.append(f"KNOWN_GAUGES entry '{name}' has no call site in the package")
    for kind in tables["records"]:
        if kind not in usage.kinds:
            errors.append(f"RECORD_SCHEMA kind '{kind}' is never written in the package")
    return errors


class TelemetryChecker(Checker):
    name = "telemetry"
    rules = (
        "telemetry-undeclared-name",
        "telemetry-missing-field",
        "telemetry-dead-declaration",
    )

    def __init__(self):
        self._tables: dict | None = None
        self._usage = Usage()
        self._decl_file = "dolomite_engine_tpu/utils/telemetry.py"

    def start(self, repo_root: str) -> None:
        self._tables = load_tables()
        self._usage = Usage()

    def visit_file(self, f: SourceFile) -> list[Finding]:
        if not f.rel.startswith("dolomite_engine_tpu/"):
            return []
        errors, usage = scan_tree(f.tree, f.path, self._tables)
        self._usage.update(usage)
        return [
            Finding(
                "telemetry-missing-field" if "missing required field" in msg else (
                    "telemetry-undeclared-name"
                ),
                f.rel,
                line,
                msg,
            )
            for line, msg in errors
        ]

    def finalize(self) -> list[Finding]:
        return [
            Finding("telemetry-dead-declaration", self._decl_file, 1, msg)
            for msg in reverse_errors(self._tables, self._usage)
        ]
