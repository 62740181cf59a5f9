"""The :class:`Finding` record every graftlint pass emits.

A finding is one diagnosed site: rule id, severity, ``file:line``, a
message, and two identity fields — the enclosing ``scope`` (module /
``Class.method`` qualname) and the stripped source ``code`` line.  The
identity triple ``(rule, file, scope, code)`` is what the baseline file
matches on: line numbers shift whenever anything above them is edited,
so a baseline keyed on them would go stale on every unrelated diff,
while the scope+code pair survives reflows and stays reviewable (the
baseline entry quotes the exact code it excuses).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["Finding", "SEVERITIES", "render_human", "render_json",
           "counts_of"]

# ordered most → least severe; "error" fails the fatal lint, "warning"
# is advisory, "info" is reporting (per-program stats, counts)
SEVERITIES = ("error", "warning", "info")


class Finding:
    """One diagnosed site.  Plain object: thousands may be created on a
    whole-tree run."""

    __slots__ = ("rule", "severity", "file", "line", "message",
                 "scope", "code", "suppressed")

    def __init__(self, rule: str, severity: str, file: str, line: int,
                 message: str, scope: str = "", code: str = ""):
        if severity not in SEVERITIES:
            raise ValueError(f"severity {severity!r} not in {SEVERITIES}")
        self.rule = rule
        self.severity = severity
        self.file = file
        self.line = int(line)
        self.message = message
        self.scope = scope
        self.code = code
        # None = active; "pragma" / "baseline" once suppressed
        self.suppressed: Optional[str] = None

    def key(self) -> Dict[str, str]:
        """The baseline-matching identity (no line number — see module
        docstring)."""
        return {"rule": self.rule, "file": self.file,
                "scope": self.scope, "code": self.code}

    def to_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "severity": self.severity,
                "file": self.file, "line": self.line,
                "message": self.message, "scope": self.scope,
                "code": self.code, "suppressed": self.suppressed}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Finding({self.rule}, {self.severity}, "
                f"{self.file}:{self.line}, {self.message[:40]!r})")


def counts_of(findings: Iterable[Finding]) -> Dict[str, int]:
    out = {s: 0 for s in SEVERITIES}
    out["suppressed"] = 0
    for f in findings:
        if f.suppressed:
            out["suppressed"] += 1
        else:
            out[f.severity] += 1
    return out


def render_human(findings: List[Finding],
                 show_suppressed: bool = False) -> List[str]:
    """One ``graftlint: <sev>: file:line: [rule] message`` line per
    finding, errors first, then file order."""
    order = {s: i for i, s in enumerate(SEVERITIES)}
    lines = []
    for f in sorted(findings, key=lambda f: (order[f.severity], f.file,
                                             f.line, f.rule)):
        if f.suppressed and not show_suppressed:
            continue
        tag = (f" (suppressed: {f.suppressed})" if f.suppressed else "")
        lines.append(f"graftlint: {f.severity}: {f.file}:{f.line}: "
                     f"[{f.rule}] {f.message}{tag}")
    return lines


def render_json(findings: List[Finding],
                meta: Optional[Dict[str, Any]] = None) -> str:
    """The machine report (``--json``): counts + every finding
    including suppressed ones, so lint debt is countable, not just a
    pass/fail bit."""
    doc = {
        "schema": "graftlint_report",
        "version": 1,
        "counts": counts_of(findings),
        "findings": [f.to_dict() for f in findings],
    }
    if meta:
        doc.update(meta)
    return json.dumps(doc, indent=2, sort_keys=True)
