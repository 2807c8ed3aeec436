"""MN: static metric-name lint (former tools/check_metric_names.py).

Every static series name passed to `metrics.inc/observe/observe_many/
gauge_set` must be `declare()`d in the metric-kind registry
(emqx_tpu/broker/metrics.py) — an undeclared series silently renders no
`# TYPE` line and is invisible to every dashboard, exporter, and alarm.

Unlike the old script this collects the declared set *statically* (every
`declare("name", ...)` call in the scanned tree), so the analyzer never
imports broker code. Dynamic names (f-strings, variables) are skipped —
they must be composed from declared prefixes, e.g. the
`ingest.lane.settle.seconds.<lane>` family, each declared explicitly.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Sequence, Set, Tuple

from tools.analysis.core import (
    Checker,
    Finding,
    ParsedModule,
    enclosing_symbols,
)

METHODS = ("inc", "observe", "observe_many", "gauge_set")


def declared_names(modules: Sequence[ParsedModule]) -> Set[str]:
    """Every `declare("<name>", ...)` first-arg string in the tree."""
    out: Set[str] = set()
    for mod in modules:
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Call)
                and (
                    (isinstance(node.func, ast.Name)
                     and node.func.id == "declare")
                    or (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "declare")
                )
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                out.add(node.args[0].value)
    return out


def call_sites(mod: ParsedModule) -> List[Tuple[int, str]]:
    """[(lineno, name)] for every static-name metric call in a module."""
    sites = []
    for node in ast.walk(mod.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in METHODS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            sites.append((node.lineno, node.args[0].value))
    return sites


class MetricNameChecker(Checker):
    name = "metrics"
    codes = {
        "MN001": "metric series name not declared in the metric-kind "
                 "registry",
    }

    def begin(self, modules: Sequence[ParsedModule]) -> None:
        self._declared = declared_names(modules)

    def check(self, mod: ParsedModule) -> Iterable[Finding]:
        findings: List[Finding] = []
        syms = enclosing_symbols(mod.tree)

        def nearest_symbol(lineno, end):
            best = "<module>"
            for n, s in syms.items():
                if n.lineno <= lineno and \
                        getattr(n, "end_lineno", 1 << 30) >= end:
                    best = s
            return best

        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in METHODS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value not in self._declared
            ):
                name = node.args[0].value
                findings.append(Finding(
                    code="MN001",
                    path=mod.rel,
                    line=node.lineno,
                    symbol=nearest_symbol(
                        node.lineno, node.end_lineno or node.lineno
                    ),
                    detail=name,
                    message=(
                        f"undeclared metric name {name!r}; declare() it "
                        "in emqx_tpu/broker/metrics.py"
                    ),
                ))
        return findings
