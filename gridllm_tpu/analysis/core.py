"""Static-analysis core (ISSUE 8): repo loader, rule registry, findings.

The analyzer is AST-based and import-light by design: it parses source
text, never imports the modules it checks (except ``utils/config.py``'s
pure-data env registry), and never touches jax — so ``python -m
gridllm_tpu.analysis`` is safe to run on a control-plane host, in CI, and
as a pre-commit hook, in well under a second.

A rule is a function ``check(repo) -> list[Finding]`` registered via the
:func:`rule` decorator. Rules live in ``gridllm_tpu/analysis/rules/`` and
are discovered by import; adding a rule is adding a module there (see
README "Static analysis & sanitizers").
"""

from __future__ import annotations

import ast
import dataclasses
import importlib
import pkgutil
import time
from pathlib import Path
from typing import Any, Callable, Iterator

# directories the repo walker ignores outright
_SKIP_DIRS = {"__pycache__", ".git", ".github", "node_modules", ".claude"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One defect at one location. ``path`` is repo-relative."""

    rule: str
    path: str
    line: int
    message: str

    @property
    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class SourceFile:
    """One parsed python file. The AST is parsed + parent-annotated ONCE
    (``.parent`` back-references let rules walk upward — enclosing
    with/try/def) and the flattened node list is cached, so all rules
    share one parse and one tree walk per file instead of redoing either
    per rule."""

    def __init__(self, root: Path, path: Path):
        self.path = path
        self.rel = path.relative_to(root).as_posix()
        self.text = path.read_text(encoding="utf-8", errors="replace")
        self._tree: ast.Module | None = None
        self._nodes: list[ast.AST] | None = None
        self._lines: list[str] | None = None
        self.parse_error: SyntaxError | None = None

    @property
    def lines(self) -> list[str]:
        """Split source lines, cached — waiver-comment lookups run once
        per candidate node, and re-splitting the text each time is
        O(file × nodes) waste."""
        if self._lines is None:
            self._lines = self.text.splitlines()
        return self._lines

    @property
    def tree(self) -> ast.Module | None:
        if self._tree is None and self.parse_error is None:
            try:
                self._tree = ast.parse(self.text, filename=self.rel)
            except SyntaxError as e:  # surfaced as a finding by run()
                self.parse_error = e
                return None
            for node in ast.walk(self._tree):
                for child in ast.iter_child_nodes(node):
                    child.parent = node  # type: ignore[attr-defined]
        return self._tree

    def walk(self) -> Iterator[ast.AST]:
        if self._nodes is None:
            tree = self.tree
            self._nodes = [] if tree is None else list(ast.walk(tree))
        return iter(self._nodes)


class Repo:
    """The analyzed tree: every .py file under the package, tests and
    deploy scripts, plus raw-text access to non-python artifacts
    (dashboards, alerts, README)."""

    def __init__(self, root: Path):
        self.root = Path(root).resolve()
        self.files: list[SourceFile] = []
        for sub in ("gridllm_tpu", "tests", "deploy"):
            base = self.root / sub
            if base.is_dir():
                for p in sorted(base.rglob("*.py")):
                    if not _SKIP_DIRS.intersection(p.parts):
                        self.files.append(SourceFile(self.root, p))
        self._by_rel = {f.rel: f for f in self.files}
        # parse + parent-annotate every file ONCE, here in the loader —
        # the trees (and cached node lists) are shared by all rules;
        # syntax errors surface exactly once as findings in run()
        for f in self.files:
            f.tree

    def file(self, rel: str) -> SourceFile | None:
        return self._by_rel.get(rel)

    def package_files(self, include_tests: bool = False) -> list[SourceFile]:
        out = [f for f in self.files if f.rel.startswith("gridllm_tpu/")]
        if include_tests:
            out += [f for f in self.files if f.rel.startswith("tests/")]
        return out

    def read_text(self, rel: str) -> str | None:
        p = self.root / rel
        if not p.is_file():
            return None
        return p.read_text(encoding="utf-8", errors="replace")


# -- rule registry ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    description: str
    check: Callable[[Repo], list[Finding]]


RULES: dict[str, Rule] = {}


def rule(name: str, description: str):
    """Register ``check(repo) -> list[Finding]`` under ``name``."""

    def deco(fn: Callable[[Repo], list[Finding]]):
        RULES[name] = Rule(name, description, fn)
        return fn

    return deco


def load_rules() -> None:
    """Import every module in gridllm_tpu.analysis.rules (side effect:
    the @rule decorators populate RULES)."""
    from gridllm_tpu.analysis import rules as rules_pkg

    for mod in pkgutil.iter_modules(rules_pkg.__path__):
        importlib.import_module(f"{rules_pkg.__name__}.{mod.name}")


def run(root: str | Path, rule_names: list[str] | None = None) -> list[Finding]:
    """Run the selected rules (default: all) over the repo at ``root``."""
    return run_timed(root, rule_names)[0]


def run_timed(
    root: str | Path, rule_names: list[str] | None = None,
) -> tuple[list[Finding], dict[str, float]]:
    """Like :func:`run`, also returning per-rule wall time in seconds —
    surfaced in the CLI's ``--json`` output so CI can spot a rule whose
    cost regressed (the repo loader parses every tree once up front;
    a slow rule is a slow RULE, not a re-parse)."""
    load_rules()
    t0 = time.perf_counter()
    repo = Repo(Path(root))
    timings: dict[str, float] = {"_load": time.perf_counter() - t0}
    findings: list[Finding] = []
    for f in repo.files:
        if f.parse_error is not None:
            findings.append(Finding(
                "parse", f.rel, f.parse_error.lineno or 0,
                f"syntax error: {f.parse_error.msg}"))
    names = rule_names if rule_names else sorted(RULES)
    for name in names:
        if name not in RULES:
            raise KeyError(f"unknown rule {name!r}; known: {sorted(RULES)}")
        t0 = time.perf_counter()
        findings.extend(RULES[name].check(repo))
        timings[name] = time.perf_counter() - t0
    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings, timings


# -- shared AST helpers -----------------------------------------------------

def dotted_name(node: ast.AST) -> str:
    """Best-effort dotted name of an expression: ``self.alloc.free`` →
    "self.alloc.free"; non-name parts render as ``?``."""
    if isinstance(node, ast.Attribute):
        return f"{dotted_name(node.value)}.{node.attr}"
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call):
        return f"{dotted_name(node.func)}()"
    return "?"


def str_const(node: ast.AST | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def ancestors(node: ast.AST) -> Iterator[ast.AST]:
    cur = getattr(node, "parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "parent", None)


def enclosing_function(node: ast.AST) -> ast.AST | None:
    for anc in ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return anc
    return None


@dataclasses.dataclass(frozen=True)
class MetricReg:
    """One ``registry.counter/gauge/histogram("name", "help", (labels,))``
    call site found statically."""

    name: str
    kind: str                      # counter | gauge | histogram
    help: str | None               # None when not a string literal
    labels: tuple[str, ...] | None  # None when not a literal tuple
    file: str
    line: int


_METRIC_KINDS = {"counter", "gauge", "histogram"}


def _call_arg(node: ast.Call, idx: int, kw_name: str) -> ast.AST | None:
    """The expression bound to a parameter, whether passed positionally
    (``idx``) or by keyword (``kw_name``); None when absent."""
    if len(node.args) > idx:
        return node.args[idx]
    for kw in node.keywords:
        if kw.arg == kw_name:
            return kw.value
    return None


def collect_metric_registrations(repo: Repo) -> list[MetricReg]:
    """Every metric-registration call in the package (tests excluded):
    a ``.counter(``/``.gauge(``/``.histogram(`` call whose name argument
    is a ``gridllm_``-prefixed string literal, plus any whose receiver
    looks like a metrics registry (so misnamed metrics still surface).
    Arguments count whether positional or keyword (``labelnames=...``)."""
    out: list[MetricReg] = []
    for f in repo.package_files():
        for node in f.walk():
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_KINDS
                    and (node.args or node.keywords)):
                continue
            name = str_const(_call_arg(node, 0, "name"))
            recv = dotted_name(node.func.value).lower()
            registryish = ("registry" in recv or "metrics" in recv
                           or recv == "m" or "_obs" in recv)
            if name is None or not (name.startswith("gridllm_")
                                    or registryish):
                continue
            help_text = str_const(_call_arg(node, 1, "help"))
            labels_expr = _call_arg(node, 2, "labelnames")
            labels: tuple[str, ...] | None
            if labels_expr is None:
                # no labels passed at all — unless a **kwargs splat could
                # be smuggling some, in which case nothing can be audited
                splat = any(kw.arg is None for kw in node.keywords)
                labels = None if splat else ()
            elif isinstance(labels_expr, (ast.Tuple, ast.List)):
                vals = [str_const(e) for e in labels_expr.elts]
                labels = (tuple(v for v in vals if v is not None)
                          if all(v is not None for v in vals) else None)
            else:
                labels = None  # non-literal labels: unauditable, flagged
            out.append(MetricReg(name or "?", node.func.attr, help_text,
                                 labels, f.rel, node.lineno))
    return out
