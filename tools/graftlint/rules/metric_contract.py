"""metric-contract: telemetry declarations vs call sites vs dashboards.

Three artifacts must agree on every metric family:

1. the inventory — ``telemetry.py``'s ``_HELP`` table (every family the
   exposition documents), plus families synthesized directly as
   exposition text (``# HELP <name> ...`` string literals);
2. the emitters — ``inc``/``observe``/``set_gauge``/``span``/
   ``bound_span``/``_observe_key``/``_hist_handle`` call sites across the package,
   including one-level wrappers (a function whose parameter flows into
   the name position collects its call-site literals — how the
   slot-phase families reach ``observe``) and module-level key-tuple
   constants (``_ADMIT_APPLY_KEY``);
3. the dashboards — every series a Grafana panel references
   (``metrics/grafana/**/*.json`` expr strings, with ``_bucket``/
   ``_sum``/``_count`` folded onto their histogram family, plus the
   labels its ``by (...)`` clauses and ``{{legend}}`` templates assume);
4. the SLO definitions — every ``SloDef(...)`` call site's ``family``
   (slo.py's DEFAULT_SLOS and any ad-hoc definition in the package): a
   budget over a series no call site emits as a histogram is a gate that
   can never fire — it evaluates to permanent ``no_data`` green, the
   silent-dashboard failure mode wearing a pass/fail costume.

Findings: a family emitted but missing from the inventory; a family
declared but never emitted (dead HELP text — or a typo'd emitter); a
dashboard series that no code emits (the silent-dashboard failure mode:
panels render empty and nobody notices); a dashboard label no emitter
ever attaches; an SLO definition over a never-emitted (or
non-histogram) family.  Span families are checked with their
``_seconds`` suffix.  Label semantics are union-based: a label is
satisfied if ANY call site of the family attaches it (per-site label
variance is a legitimate pattern here — drain-level vs item-level error
counts).
"""

from __future__ import annotations

import ast
import json
import re

from ..core import Finding, Module, Project
from .common import call_name, module_functions, walk_excluding_nested

_EMIT_METHODS = {"inc", "observe", "set_gauge", "span", "bound_span"}
_SPAN_METHODS = {"span", "bound_span"}
_NON_LABEL_KWARGS = {"value", "slow"}
_HELP_RE = re.compile(r"# HELP (\w+) ")

# PromQL tokens that are not metric names
_PROMQL_NOISE = {
    "histogram_quantile", "label_replace", "label_join", "group_left",
    "group_right", "clamp_max", "clamp_min", "count_values", "absent_over_time",
    "avg_over_time", "max_over_time", "min_over_time", "sum_over_time",
    "rate", "irate", "increase", "delta", "idelta", "deriv", "resets",
    "sum", "avg", "min", "max", "count", "topk", "bottomk", "stddev", "stdvar",
    "by", "without", "on", "ignoring", "offset", "bool", "and", "or", "unless",
    "abs", "ceil", "floor", "round", "exp", "ln", "log2", "log10", "sqrt",
    "time", "vector", "scalar", "sort", "sort_desc", "absent", "changes",
}

_BY_CLAUSE_RE = re.compile(r"\b(?:by|without)\s*\(([^)]*)\)")
_SELECTOR_RE = re.compile(r"\{([^}]*)\}")
_LEGEND_RE = re.compile(r"\{\{\s*(\w+)\s*\}\}")
_IDENT_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*")


class MetricContractRule:
    name = "metric-contract"
    description = "metric families/labels consistent across telemetry, code, dashboards"

    def __init__(self, dashboards_glob: str = "metrics/grafana/**/*.json"):
        self.dashboards_glob = dashboards_glob

    def check(self, project: Project) -> list[Finding]:
        telemetry = self._find_telemetry(project)
        declared, help_line = self._declared(telemetry) if telemetry else ({}, 1)
        emitted = self._emitted(project)  # family -> {"labels", "kinds", "site"}
        synthesized = self._synthesized(telemetry) if telemetry else set()
        for fam in synthesized:
            declared.setdefault(fam, help_line)
            emitted.setdefault(fam, {"labels": set(), "kinds": {"gauge"}, "site": None})

        findings: list[Finding] = []
        tel_rel = telemetry.rel if telemetry else "telemetry.py"
        for fam, info in sorted(emitted.items()):
            if fam not in declared and info["site"] is not None:
                rel, line = info["site"]
                findings.append(
                    Finding(
                        rule=self.name,
                        path=rel,
                        line=line,
                        message=(
                            f"metric family {fam!r} is emitted here but missing "
                            "from telemetry._HELP — the exposition will carry "
                            "a name-only HELP line and the inventory drifts"
                        ),
                    )
                )
        for fam, line in sorted(declared.items()):
            if fam not in emitted:
                findings.append(
                    Finding(
                        rule=self.name,
                        path=tel_rel,
                        line=line,
                        message=(
                            f"metric family {fam!r} is declared in telemetry._HELP "
                            "but no call site emits it — dead inventory or a "
                            "typo'd emitter"
                        ),
                    )
                )
        hist_families = {f for f, i in emitted.items() if "histogram" in i["kinds"]}
        findings.extend(self._check_dashboards(project, emitted, hist_families))
        findings.extend(
            self._check_slo_definitions(project, emitted, hist_families)
        )
        return findings

    # -------------------------------------------------------- SLO contract

    def _check_slo_definitions(
        self, project: Project, emitted: dict, hist_families: set
    ) -> list[Finding]:
        """Every ``SloDef(...)`` family literal must be an emitted
        HISTOGRAM family — an SLO over a never-emitted series evaluates
        to permanent no_data and the gate silently never fires."""
        findings: list[Finding] = []
        for module in project.modules:
            for node in ast.walk(module.tree):
                if not (isinstance(node, ast.Call) and call_name(node) == "SloDef"):
                    continue
                family = None
                for kw in node.keywords:
                    if kw.arg == "family" and isinstance(kw.value, ast.Constant):
                        family = kw.value.value
                if family is None and len(node.args) >= 2:
                    arg = node.args[1]  # SloDef(name, family, quantile, budget)
                    if isinstance(arg, ast.Constant):
                        family = arg.value
                if not isinstance(family, str):
                    continue
                if family not in emitted:
                    findings.append(
                        Finding(
                            rule=self.name,
                            path=module.rel,
                            line=node.lineno,
                            message=(
                                f"SLO definition references family {family!r} "
                                "but no call site emits it — the budget "
                                "evaluates to permanent no_data and the gate "
                                "never fires"
                            ),
                        )
                    )
                elif family not in hist_families:
                    findings.append(
                        Finding(
                            rule=self.name,
                            path=module.rel,
                            line=node.lineno,
                            message=(
                                f"SLO definition references {family!r}, which "
                                "is emitted but not as a histogram — quantile "
                                "budgets need a distribution"
                            ),
                        )
                    )
        return findings

    # -------------------------------------------------------------- sources

    def _find_telemetry(self, project: Project) -> Module | None:
        candidates = [m for m in project.modules if m.rel.endswith("telemetry.py")]
        if not candidates:
            return None
        # prefer the package-level module (shortest path), not re-exports
        return min(candidates, key=lambda m: len(m.rel))

    def _declared(self, telemetry: Module) -> tuple[dict[str, int], int]:
        """_HELP dict literal: family -> declaration line."""
        declared: dict[str, int] = {}
        help_line = 1
        for node in telemetry.tree.body:
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "_HELP" for t in node.targets
            ):
                help_line = node.lineno
                if isinstance(node.value, ast.Dict):
                    for key in node.value.keys:
                        if isinstance(key, ast.Constant) and isinstance(key.value, str):
                            declared[key.value] = key.lineno
        return declared, help_line

    def _synthesized(self, telemetry: Module) -> set[str]:
        """Families emitted as raw exposition text (# HELP lines)."""
        out: set[str] = set()
        for node in ast.walk(telemetry.tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                for m in _HELP_RE.finditer(node.value):
                    out.add(m.group(1))
        return out

    def _emitted(self, project: Project) -> dict[str, dict]:
        emitted: dict[str, dict] = {}

        def note(fam: str, labels, kind: str, rel: str, line: int) -> None:
            info = emitted.setdefault(
                fam, {"labels": set(), "kinds": set(), "site": (rel, line)}
            )
            info["labels"].update(labels)
            info["kinds"].add(kind)

        # pass 1: literal emissions + wrapper discovery
        wrappers: dict[str, int] = {}  # function name -> name-param index
        for module in project.modules:
            for fi in module_functions(module):
                params = [a.arg for a in fi.node.args.args]
                for node in walk_excluding_nested(fi.node):
                    if not isinstance(node, ast.Call):
                        continue
                    cname = call_name(node)
                    if cname in _EMIT_METHODS and node.args:
                        labels = {
                            kw.arg
                            for kw in node.keywords
                            if kw.arg and kw.arg not in _NON_LABEL_KWARGS
                        }
                        kind = {
                            "inc": "counter",
                            "set_gauge": "gauge",
                        }.get(cname, "histogram")
                        arg0 = node.args[0]
                        if isinstance(arg0, ast.Constant) and isinstance(arg0.value, str):
                            fam = arg0.value
                            if cname in _SPAN_METHODS:
                                fam += "_seconds"
                            note(fam, labels, kind, module.rel, node.lineno)
                        elif (
                            isinstance(arg0, ast.Name)
                            and arg0.id in params
                            and fi.name not in _EMIT_METHODS
                        ):
                            # a wrapper function forwarding a name param —
                            # but not the registry methods/helpers
                            # themselves (their call sites are pass 1)
                            wrappers[fi.name] = params.index(arg0.id)
                    elif cname in ("_observe_key", "_hist_handle") and node.args:
                        fam = self._key_tuple_family(node.args[0], module)
                        if fam:
                            note(fam, set(), "histogram", module.rel, node.lineno)
        # pass 2: wrapper call sites contribute their literal names
        if wrappers:
            for module in project.modules:
                for fi in module_functions(module):
                    for node in walk_excluding_nested(fi.node):
                        if not isinstance(node, ast.Call):
                            continue
                        cname = call_name(node)
                        idx = wrappers.get(cname or "")
                        if idx is None or len(node.args) <= idx:
                            continue
                        arg = node.args[idx]
                        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                            note(arg.value, set(), "histogram", module.rel, node.lineno)
        return emitted

    def _key_tuple_family(self, arg: ast.AST, module: Module) -> str | None:
        """``("family", ...)`` inline, or a module-level NAME bound to one."""
        if isinstance(arg, ast.Name):
            for node in module.tree.body:
                if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == arg.id for t in node.targets
                ):
                    arg = node.value
                    break
        if (
            isinstance(arg, ast.Tuple)
            and arg.elts
            and isinstance(arg.elts[0], ast.Constant)
            and isinstance(arg.elts[0].value, str)
        ):
            return arg.elts[0].value
        return None

    # ----------------------------------------------------------- dashboards

    def _check_dashboards(
        self, project: Project, emitted: dict, hist_families: set
    ) -> list[Finding]:
        findings: list[Finding] = []
        for path in sorted(project.root.glob(self.dashboards_glob)):
            try:
                text = path.read_text()
                data = json.loads(text)
            except (OSError, json.JSONDecodeError):
                continue
            rel = path.relative_to(project.root).as_posix()
            raw_lines = text.splitlines()
            for expr, legend in self._dashboard_exprs(data):
                line = self._locate(raw_lines, expr)
                fams = self._expr_families(expr)
                labels = set(_LEGEND_RE.findall(legend or ""))
                for m in _BY_CLAUSE_RE.finditer(expr):
                    labels.update(
                        t.strip() for t in m.group(1).split(",") if t.strip()
                    )
                labels.discard("le")
                for fam, stripped in fams:
                    # an exact family match wins (plenty of counters end in
                    # _count); only then try the histogram-suffix fold
                    if fam in emitted:
                        base = fam
                    elif fam != stripped and stripped in emitted:
                        base = stripped
                        if stripped not in hist_families:
                            findings.append(
                                Finding(
                                    rule=self.name,
                                    path=rel,
                                    line=line,
                                    message=(
                                        f"dashboard series {fam!r} implies a "
                                        f"histogram but {stripped!r} is not "
                                        "emitted as one"
                                    ),
                                )
                            )
                    else:
                        findings.append(
                            Finding(
                                rule=self.name,
                                path=rel,
                                line=line,
                                message=(
                                    f"dashboard series {fam!r} is never emitted "
                                    "by any call site — the panel renders empty"
                                ),
                            )
                        )
                        continue
                    emitted_labels = emitted[base]["labels"]
                    for lab in sorted(labels):
                        if lab and lab not in emitted_labels:
                            findings.append(
                                Finding(
                                    rule=self.name,
                                    path=rel,
                                    line=line,
                                    message=(
                                        f"dashboard references label {lab!r} on "
                                        f"{base!r} but no call site attaches it"
                                    ),
                                )
                            )
        return findings

    def _dashboard_exprs(self, data):
        """(expr, legendFormat) pairs from a Grafana dashboard JSON."""
        out = []

        def walk(node):
            if isinstance(node, dict):
                if "expr" in node and isinstance(node["expr"], str):
                    out.append((node["expr"], node.get("legendFormat", "")))
                for v in node.values():
                    walk(v)
            elif isinstance(node, list):
                for v in node:
                    walk(v)

        walk(data)
        return out

    def _expr_families(self, expr: str) -> list[tuple[str, str]]:
        """(series name, base family) references in one PromQL expr."""
        # strip label selectors and by-clauses so their names don't count
        cleaned = _BY_CLAUSE_RE.sub(" ", expr)
        cleaned = _SELECTOR_RE.sub(" ", cleaned)
        out = []
        for tok in _IDENT_RE.findall(cleaned):
            if tok in _PROMQL_NOISE or "_" not in tok:
                continue
            base = tok
            for suffix in ("_bucket", "_sum", "_count"):
                if tok.endswith(suffix):
                    base = tok[: -len(suffix)]
                    break
            out.append((tok, base))
        return out

    @staticmethod
    def _locate(lines: list[str], needle: str) -> int:
        probe = needle[:60]
        for i, line in enumerate(lines, 1):
            if probe in line:
                return i
        return 1
