"""Reviewed suppressions for syndeo-lint findings.

``analysis/baseline.toml`` holds ``[[suppress]]`` entries::

    [[suppress]]
    rule = "SYN-L001"
    file = "worker.py"            # path suffix match
    function = "HeadServer.dispatch"   # optional, exact qualname
    match = "c.store.get"         # optional, message substring
    reason = "relay path: head-local store, bounded control ops"

``reason`` is mandatory: a suppression without a written justification
is a bug, not a baseline.  Parsed with :mod:`tomllib`.
"""
from __future__ import annotations

import tomllib
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.model import Finding

_REQUIRED = ("rule", "file", "reason")
_OPTIONAL = ("function", "match")


def load_baseline(path: str) -> List[Dict[str, str]]:
    data = tomllib.loads(Path(path).read_text())
    entries = data.get("suppress", [])
    if not isinstance(entries, list):
        raise ValueError("baseline: [[suppress]] must be an array of "
                         "tables")
    for i, e in enumerate(entries):
        if not isinstance(e, dict):
            raise ValueError(f"baseline: suppress[{i}] is not a table")
        for k in _REQUIRED:
            if not isinstance(e.get(k), str) or not e[k]:
                raise ValueError(
                    f"baseline: suppress[{i}] needs non-empty "
                    f"string {k!r}")
        for k in e:
            if k not in _REQUIRED + _OPTIONAL:
                raise ValueError(
                    f"baseline: suppress[{i}] has unknown key {k!r}")
    return entries


def apply_baseline(
    findings: Sequence[Finding], entries: Sequence[Dict[str, str]],
) -> Tuple[List[Finding], List[Finding], List[Dict[str, str]]]:
    """Split findings into (unsuppressed, suppressed, unused entries)."""
    used: Set[int] = set()
    unsup: List[Finding] = []
    sup: List[Finding] = []
    for f in findings:
        idx = _match(f, entries)
        if idx is None:
            unsup.append(f)
        else:
            used.add(idx)
            sup.append(f)
    unused = [e for i, e in enumerate(entries) if i not in used]
    return unsup, sup, unused


def _match(f: Finding,
           entries: Sequence[Dict[str, str]]) -> Optional[int]:
    for i, e in enumerate(entries):
        if e["rule"] != f.rule:
            continue
        if not f.file.endswith(e["file"]):
            continue
        if e.get("function") and e["function"] != f.function:
            continue
        if e.get("match") and e["match"] not in f.message:
            continue
        return i
    return None

