"""telemetry-names pass: every metric/span family emitted anywhere in
the package must be KNOWN to ``tools/telemetry_report.py``.

The report tool is the one place operators look; a metric emitted under
a family the tool has never heard of silently vanishes from every
report (the PR-1..PR-8 family sections each had to remember to add
themselves). The tool now declares its registry
(``KNOWN_METRIC_FAMILIES`` / ``KNOWN_SPAN_FAMILIES``) and this pass
closes the loop:

- any ``counter("x/...")``/``gauge``/``histogram`` emission whose family
  ``x`` is not in ``KNOWN_METRIC_FAMILIES`` is an orphan;
- any ``span("y....")``/``instant``/``phase`` emission whose family
  ``y`` is not in ``KNOWN_SPAN_FAMILIES`` is an orphan;
- any family the tool declares but nothing emits is dead registry.

Only literal names are collected (f-string families are already pinned
by their literal prefix elsewhere or out of scope by construction).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from ..core import AnalysisPass, register
from .. import ast_driver as _ad

REPORT_TOOL = "tools/telemetry_report.py"
SCAN_DIRS = ("mxnet_tpu", "tools", "benchmarks")

METRIC_EMITTERS = {"counter", "gauge", "histogram"}
SPAN_EMITTERS = {"span", "instant", "phase"}  # phase: "mxtpu." + name


def collect_emissions(index: _ad.AstIndex):
    """(metric_families, span_families): family -> [(path, line, name)]."""
    metrics: Dict[str, List] = {}
    spans: Dict[str, List] = {}
    for rel in index.package_files(*SCAN_DIRS):
        if rel == REPORT_TOOL:
            continue
        try:
            mod = index.module(rel)
        except SyntaxError:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            first = node.args[0]
            if not isinstance(first, ast.Constant) or \
                    not isinstance(first.value, str):
                continue
            attr = getattr(node.func, "attr", None) or \
                getattr(node.func, "id", None)
            if attr in METRIC_EMITTERS and "/" in first.value:
                fam = first.value.split("/")[0]
                metrics.setdefault(fam, []).append(
                    (rel, node.lineno, first.value))
            elif attr in SPAN_EMITTERS and "." in first.value:
                fam = first.value.split(".")[0]
                spans.setdefault(fam, []).append(
                    (rel, node.lineno, first.value))
    return metrics, spans


def declared_families(index: _ad.AstIndex) -> Tuple[Set[str], Set[str],
                                                    Dict[str, int]]:
    """Families the report tool declares, parsed from its AST (the tool
    is a script, not an importable package module)."""
    mod = index.module(REPORT_TOOL)
    out = {"KNOWN_METRIC_FAMILIES": set(), "KNOWN_SPAN_FAMILIES": set()}
    lines: Dict[str, int] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id in out:
                v = node.value
                keys = []
                if isinstance(v, ast.Dict):
                    keys = v.keys
                elif isinstance(v, (ast.Set, ast.List, ast.Tuple)):
                    keys = v.elts
                for k in keys:
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str):
                        out[t.id].add(k.value)
                        lines[k.value] = k.lineno
    return (out["KNOWN_METRIC_FAMILIES"], out["KNOWN_SPAN_FAMILIES"],
            lines)


@register
class TelemetryNamesPass(AnalysisPass):
    name = "telemetry-names"
    ir = "meta"
    description = ("every emitted metric/span family is known to "
                   "tools/telemetry_report.py (and none is dead)")

    def run(self, ctx):
        findings = []
        metrics, spans = collect_emissions(ctx.ast)
        known_m, known_s, decl_lines = declared_families(ctx.ast)
        if not known_m:
            return [self.finding(
                "registry-missing", REPORT_TOOL, 0, key="KNOWN_FAMILIES",
                message=f"{REPORT_TOOL} declares no "
                "KNOWN_METRIC_FAMILIES — the report tool lost its "
                "family registry")]
        for fam, sites in sorted(metrics.items()):
            if fam not in known_m:
                path, ln, name = sites[0]
                findings.append(self.finding(
                    "orphan-metric", path, ln, key=f"metric:{fam}",
                    message=f"metric family {fam}/ (e.g. {name!r} at "
                    f"{path}:{ln}) is emitted but unknown to "
                    f"{REPORT_TOOL} — it vanishes from every report"))
        for fam, sites in sorted(spans.items()):
            if fam not in known_s:
                path, ln, name = sites[0]
                findings.append(self.finding(
                    "orphan-span", path, ln, key=f"span:{fam}",
                    message=f"span family {fam}.* (e.g. {name!r} at "
                    f"{path}:{ln}) is emitted but unknown to "
                    f"{REPORT_TOOL}"))
        for fam in sorted(known_m - set(metrics)):
            findings.append(self.finding(
                "dead-family", REPORT_TOOL, decl_lines.get(fam, 0),
                key=f"dead-metric:{fam}",
                message=f"metric family {fam}/ is declared in "
                f"{REPORT_TOOL} but nothing emits it"))
        for fam in sorted(known_s - set(spans)):
            findings.append(self.finding(
                "dead-family", REPORT_TOOL, decl_lines.get(fam, 0),
                key=f"dead-span:{fam}",
                message=f"span family {fam}.* is declared in "
                f"{REPORT_TOOL} but nothing emits it"))
        return findings
