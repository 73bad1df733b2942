"""Metrics-hygiene lint.

Every counter/timer/gauge name used anywhere in the tree must resolve
to a name declared in ``snappydata_tpu/observability/metric_names.py``
(parsed as literals — this lint never imports the package), and no two
declared-or-used names may collide after Prometheus sanitization (the
PR 10 ``_prom_name`` collision class: ``a.b`` vs ``a_b`` silently
merged before the crc-suffix fix; the lint keeps new collisions from
entering the tree at all).

Dynamic names (f-strings / ``"prefix_" + x``) are legal when their
literal prefix is declared in ``DYNAMIC_PREFIXES``; a fully-opaque
variable name needs a ``# locklint: metric=<prefix>`` hint or a
``metric-dynamic`` waiver."""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .common import Finding, SourceFile, load_sources, str_const

_KIND_OF = {"inc": "counter", "time": "timer", "record_time": "timer",
            "gauge": "gauge", "counter_fn": "counter"}
_METRIC_HINT_RE = re.compile(r"#\s*locklint:\s*metric=([A-Za-z0-9_.\-]+)")


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def load_declared(decl_path: str) -> Dict[str, Set[str]]:
    """Parse metric_names.py WITHOUT importing it: COUNTERS / TIMERS /
    GAUGES / DYNAMIC_PREFIXES must be literal set/list of strings."""
    with open(decl_path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=decl_path)
    out: Dict[str, Set[str]] = {"counter": set(), "timer": set(),
                                "gauge": set(), "prefix": set()}
    keymap = {"COUNTERS": "counter", "TIMERS": "timer", "GAUGES": "gauge",
              "DYNAMIC_PREFIXES": "prefix"}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1 \
                or not isinstance(node.targets[0], ast.Name):
            continue
        key = keymap.get(node.targets[0].id)
        if key is None:
            continue
        if not isinstance(node.value, (ast.Set, ast.List, ast.Tuple)):
            raise ValueError("%s: %s must be a literal set/list"
                             % (decl_path, node.targets[0].id))
        for el in node.value.elts:
            s = str_const(el)
            if s is None:
                raise ValueError("%s: non-literal element in %s"
                                 % (decl_path, node.targets[0].id))
            out[key].add(s)
    return out


def _name_arg(node: ast.AST) -> Tuple[Optional[str], Optional[str]]:
    """(literal_name, dynamic_prefix) for a metric-name argument."""
    s = str_const(node)
    if s is not None:
        return s, None
    if isinstance(node, ast.JoinedStr) and node.values:
        first = node.values[0]
        p = str_const(first)
        if p:
            return None, p
        return None, ""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        p = str_const(node.left)
        if p is not None:
            return None, p
        # nested concat: leftmost literal
        inner = _name_arg(node.left)
        if inner[0] is not None:
            return None, inner[0]
        if inner[1] is not None:
            return None, inner[1]
        return None, ""
    return None, None


def _is_metric_call(call: ast.Call) -> Optional[str]:
    fn = call.func
    if not isinstance(fn, ast.Attribute):
        return None
    kind = _KIND_OF.get(fn.attr)
    if kind is None:
        return None
    if not call.args:
        return None            # time.time() etc.
    if kind == "gauge" and len(call.args) < 2 and not call.keywords:
        return None
    # require a string-shaped first arg: literal, f-string, concat, or a
    # plain variable (the dynamic case)
    a0 = call.args[0]
    if isinstance(a0, (ast.Constant,)) and not isinstance(
            getattr(a0, "value", None), str):
        return None            # .time(2.0) is not a metric call
    return kind


def run(paths: List[str], decl_path: str) -> List[Finding]:
    declared = load_declared(decl_path)
    findings: List[Finding] = []
    used: Dict[str, Tuple[str, str, int]] = {}   # sanitized -> (raw, f, l)

    def check_collision(raw: str, src_path: str, line: int):
        s = _sanitize(raw)
        prev = used.get(s)
        if prev is None:
            used[s] = (raw, src_path, line)
        elif prev[0] != raw:
            findings.append(Finding(
                "metric-collision", src_path, line,
                "metric %r sanitizes to %r which %r (declared/used at "
                "%s:%d) already occupies — rename one; the runtime "
                "crc-suffix keeps exposition valid but splits the series"
                % (raw, s, prev[0], prev[1], prev[2])))

    decl_file = os.path.relpath(decl_path)
    for kind in ("counter", "timer", "gauge"):
        for name in sorted(declared[kind]):
            check_collision(name, decl_file, 1)

    sources = load_sources(paths)
    for path, src in sorted(sources.items()):
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _is_metric_call(node)
            if kind is None:
                continue
            line = node.lineno
            literal, prefix = _name_arg(node.args[0])
            if literal is not None:
                if literal not in declared[kind]:
                    # names are frequently shared across kinds (a
                    # counter mirrored by a gauge); accept any kind
                    # before failing
                    if not any(literal in declared[k]
                               for k in ("counter", "timer", "gauge")):
                        if not src.waived(line, "metric-undeclared"):
                            findings.append(Finding(
                                "metric-undeclared", path, line,
                                "%s %r is not declared in "
                                "observability/metric_names.py — add it "
                                "(and grep for near-miss spellings first)"
                                % (kind, literal)))
                check_collision(literal, path, line)
            elif prefix:
                # the site's literal chunk must extend a declared family
                # prefix (never the reverse — "f" + x matching declared
                # "fault_injected_" would void the bounded-family gate)
                if not any(prefix.startswith(p)
                           for p in declared["prefix"]):
                    if not src.waived(line, "metric-dynamic"):
                        findings.append(Finding(
                            "metric-dynamic", path, line,
                            "dynamic %s name with undeclared prefix %r — "
                            "add it to DYNAMIC_PREFIXES" % (kind, prefix)))
            else:
                hint = None
                for ln in (line, line - 1):
                    if 1 <= ln <= len(src.lines):
                        m = _METRIC_HINT_RE.search(src.lines[ln - 1])
                        if m:
                            hint = m.group(1)
                            break
                if hint is not None:
                    if hint not in declared["prefix"] and not any(
                            hint in declared[k]
                            for k in ("counter", "timer", "gauge")):
                        findings.append(Finding(
                            "metric-dynamic", path, line,
                            "metric hint %r is neither a declared name "
                            "nor a declared prefix" % hint))
                elif not src.waived(line, "metric-dynamic"):
                    findings.append(Finding(
                        "metric-dynamic", path, line,
                        "%s name is an opaque expression — add a "
                        "`# locklint: metric=<name-or-prefix>` hint "
                        "naming what flows here" % kind))
    return findings


def collect_used(paths: List[str]) -> Dict[str, Set[str]]:
    """All literal metric names in the tree, by kind — the generator the
    initial metric_names.py was seeded from (kept for re-syncing)."""
    out: Dict[str, Set[str]] = {"counter": set(), "timer": set(),
                                "gauge": set()}
    for path, src in sorted(load_sources(paths).items()):
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                kind = _is_metric_call(node)
                if kind:
                    literal, _ = _name_arg(node.args[0])
                    if literal is not None:
                        out[kind].add(literal)
    return out
