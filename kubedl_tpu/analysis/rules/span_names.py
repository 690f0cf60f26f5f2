"""KTL007 — tracing span-name drift.

Same three-surface discipline as KTL004, applied to the distributed
tracing added with the span catalog in docs/observability.md:

1. every string literal at a ``TRACER.span(...)`` / ``TRACER.begin(...)``
   / ``TRACER.record(...)`` / ``TRACER.phase(...)`` / ``TRACER.step(...)``
   call site must have a row in the
   docs/observability.md span-catalog table (the ``| Span | Layer |``
   table) — trace consumers (``scripts/tracemerge.py``, the verify
   drives, dashboards keying on span names) read that table as the
   contract;
2. every documented span name must be emitted somewhere — a stale doc
   row describes spans that no trace will ever contain.

Only the module-level ``TRACER`` singleton is matched (locally
constructed ``Tracer()`` instances in tests/benchmarks are out of
contract), and only ``kubedl_tpu/`` sources are scanned (engine policy).
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Set, Tuple

from kubedl_tpu.analysis.engine import Finding

RULE_ID = "KTL007"

DOC_PATH = "docs/observability.md"

_EMIT_METHODS = {"span", "begin", "record", "phase", "step"}

#: emission-site WRAPPERS: method name -> positional index of the span
#: name literal (JobEngine._trace_job_milestone(job, "job.submit", ...)
#: wraps TRACER.record, so its literal is part of the contract too)
_WRAPPERS = {"_trace_job_milestone": 1}


def _call_sites(contexts) -> Dict[str, List[Tuple[str, int]]]:
    """name -> [(relpath, line)] for every TRACER.span/begin/record/
    phase/step (or known wrapper) call whose span-name argument is a
    string literal."""
    out: Dict[str, List[Tuple[str, int]]] = {}
    for ctx in contexts:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if (f.attr in _EMIT_METHODS
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "TRACER"):
                idx = 0
            elif f.attr in _WRAPPERS:
                idx = _WRAPPERS[f.attr]
            else:
                continue
            if len(node.args) > idx \
                    and isinstance(node.args[idx], ast.Constant) \
                    and isinstance(node.args[idx].value, str):
                name = node.args[idx].value
                out.setdefault(name, []).append((ctx.relpath, node.lineno))
    return out


def _doc_table_spans(root: Path) -> Set[str]:
    """Backticked first-column tokens of the ``| Span | Layer |`` table."""
    doc = root / DOC_PATH
    if not doc.exists():
        return set()
    spans: Set[str] = set()
    in_table = False
    for line in doc.read_text().splitlines():
        stripped = line.strip()
        if stripped.startswith("|") and "Span" in stripped \
                and "Layer" in stripped:
            in_table = True
            continue
        if in_table:
            if not stripped.startswith("|"):
                in_table = False
                continue
            first_col = stripped.strip("|").split("|")[0]
            for tok in re.findall(r"`([^`]+)`", first_col):
                spans.add(tok.strip())
    return spans


def check_project(root: Path, contexts) -> List[Finding]:
    emitted = _call_sites(contexts)
    documented = _doc_table_spans(root)
    if not documented and not emitted:
        return []
    findings: List[Finding] = []
    if not documented:
        return [Finding(
            RULE_ID, DOC_PATH, 1,
            f"no span-catalog table (| Span | Layer | ... |) found in "
            f"{DOC_PATH} while {len(emitted)} span name(s) are emitted",
            snippet="missing-span-table",
        )]
    for name, where in sorted(emitted.items()):
        if name not in documented:
            path, line = where[0]
            findings.append(Finding(
                RULE_ID, path, line,
                f"span '{name}' emitted here but missing from the "
                f"{DOC_PATH} span catalog — document it first",
                snippet=f"undocumented-span:{name}",
            ))
    for name in sorted(documented - set(emitted)):
        findings.append(Finding(
            RULE_ID, DOC_PATH, 1,
            f"span '{name}' documented in the catalog but emitted "
            f"nowhere (stale doc row)",
            snippet=f"dead-span:{name}",
        ))
    return findings
