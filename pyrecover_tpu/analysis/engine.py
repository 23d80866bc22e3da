"""jaxlint core: file loading, suppression parsing, rule orchestration.

The engine is deliberately jax-free (pure stdlib, AST-based): it must run
in CI images without an accelerator runtime and must never pay a backend
startup to lint text. Modules are parsed once into :class:`ModuleInfo`
(AST + comment-derived suppressions/markers), indexed project-wide
(:class:`ProjectIndex` — the cross-file call-graph substrate), and every
registered rule (see ``rules.py``) runs over each module with the shared
:class:`LintContext`.

Suppression syntax (comments, parsed with ``tokenize`` so string literals
never false-match)::

    x = float(loss)   # jaxlint: disable=host-sync-in-hot-loop -- once-per-step sync
    # jaxlint: disable-next=prng-key-reuse -- fixture exercises the bug
    y = jax.random.normal(key, ())
    # jaxlint: disable-file=legacy-jax-spelling -- this module IS the shim home

Function markers steer the hot-path analysis::

    def poll_metrics(...):  # jaxlint: hot-loop     <- extra reachability seed
    def save_ckpt(...):     # jaxlint: sync-point   <- deliberate sync boundary,
                                                       pruned from the hot set
    def parse_marker(...):  # jaxlint: host-only    <- touches no device values,
                                                       pruned from the hot set
"""

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path

# The engine serves more than one analyzer: jaxlint (this package's
# original tenant), concur (analysis/concur — the concurrency-safety
# analyzer), distcheck (analysis/distcheck — the multi-host
# collective-congruence analyzer), obscheck (analysis/obscheck — the
# observability-contract analyzer), and faultcheck (analysis/faultcheck
# — the crash-consistency/fault-coverage analyzer) share the parsing,
# suppression, and marker machinery, each under its own comment
# namespace (``# jaxlint: ...`` / ``# concur: ...`` /
# ``# distcheck: ...`` / ``# obscheck: ...`` / ``# faultcheck: ...``).
# Directives (disable/disable-next/disable-file) are TOOL-SCOPED: a
# ModuleInfo parses only its own tool's suppressions, so a jaxlint
# suppression can never silence a concur or distcheck finding, or vice
# versa in every direction. Markers are parsed for EVERY registered tool
# — concur's model consumes jaxlint's ``hot-loop``/``host-only``
# reachability markers, distcheck's model consumes its own
# ``host-local`` (function returns per-host state) / ``congruent``
# (function's return agrees across hosts) declarations, obscheck
# consumes jaxlint's ``hot-loop`` reachability markers plus its own
# ``once`` marker (function emits at most once per run — a warn-once /
# once-per-run guard the AST cannot always see), faultcheck consumes
# its own ``tear-ok`` marker (function's renames publish advisory
# artifacts — torn/unsynced bytes are acceptable, so the durability
# rules stand down), and each tool simply ignores the markers it has no
# meaning for.
_MARKERS_BY_TOOL = {
    "jaxlint": r"hot-loop|sync-point|host-only",
    "concur": r"guarded-by=[\w.\-]+",
    "distcheck": r"host-local|congruent",
    "obscheck": r"once",
    "faultcheck": r"tear-ok",
}

_DIRECTIVE_RES = {}
_MARKER_RES = {}


def _directive_re(tool):
    rx = _DIRECTIVE_RES.get(tool)
    if rx is None:
        rx = _DIRECTIVE_RES[tool] = re.compile(
            rf"{tool}:\s*(disable-next|disable-file|disable)\s*=\s*"
            r"([A-Za-z0-9_\-, ]+?)\s*(?:--\s*(.*?)\s*)?$"
        )
    return rx


def _marker_res():
    if not _MARKER_RES:
        for tool, alts in _MARKERS_BY_TOOL.items():
            _MARKER_RES[tool] = re.compile(rf"{tool}:\s*({alts})\b")
    return _MARKER_RES.values()


@dataclasses.dataclass
class Finding:
    rule: str  # kebab-case rule name
    rule_id: str  # short id, e.g. JX01
    severity: str  # "error" | "warning"
    path: str  # path as given (relative when possible)
    line: int
    col: int
    message: str
    suppressed: bool = False
    justification: str = ""

    def as_dict(self):
        return dataclasses.asdict(self)

    def location(self):
        return f"{self.path}:{self.line}:{self.col}"


@dataclasses.dataclass
class LintConfig:
    """Project knowledge the pure-AST rules cannot derive on their own."""

    # rule selection (names or ids); None selects everything
    select: frozenset = None
    ignore: frozenset = frozenset()
    # host-sync rule: function names that seed hot-path reachability
    # (markers add to this set)
    hot_seeds: frozenset = frozenset({"_train_impl"})
    # factories whose RESULT is a donating jitted callable:
    # name -> tuple of donated positional indices
    donating_factories: tuple = (("make_train_step", (0,)),)
    # factories whose result dispatches device work (untimed-device-work)
    device_step_factories: frozenset = frozenset(
        {"make_train_step", "make_eval_step", "eval_loss_fn"}
    )
    # method names too generic to resolve through the fuzzy call-graph edge
    fuzzy_method_blacklist: frozenset = frozenset(
        {"get", "put", "pop", "add", "close", "start", "stop", "flush",
         "log", "read", "write", "items", "keys", "values", "append",
         "extend", "update", "join", "wait", "copy", "clear", "emit",
         "reset", "send", "next", "run"}
    )
    # the mesh axis catalog (values of the AXIS_* constants in
    # parallel/mesh.py — mirrored here because the lint engine must stay
    # importable without jax; pinned together by tests/test_jaxlint.py).
    # PartitionSpec literals naming anything else are typos that silently
    # replicate (JX09).
    pspec_axes: frozenset = frozenset(
        {"data", "fsdp", "tensor", "sequence", "pipeline", "expert"}
    )

    def rule_enabled(self, name, rule_id):
        if name in self.ignore or rule_id in self.ignore:
            return False
        if self.select is None:
            return True
        return name in self.select or rule_id in self.select


DEFAULT_CONFIG = LintConfig()


class ModuleInfo:
    """One parsed source file: AST, line table, suppressions, markers.

    ``tool`` selects which comment namespace the suppression directives
    are read from (``jaxlint`` by default; ``concur`` for the concurrency
    analyzer). Markers from every registered tool are always parsed —
    they carry cross-tool facts (reachability seeds, lock intent), not
    suppressions.
    """

    def __init__(self, path, source, relpath=None, tool="jaxlint"):
        self.path = Path(path)
        self.relpath = str(relpath if relpath is not None else path)
        self.tool = tool
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=self.relpath)
        # comment directives
        self.suppress_line = {}  # line -> (set(rules), justification)
        self.suppress_next = {}
        self.suppress_file = {}  # rule -> justification
        self.markers = {}  # line -> set(marker)
        self._scan_comments()
        # parent links for ancestor queries (loops, enclosing defs)
        self.parents = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # physical line -> first line of the innermost statement covering
        # it, so a suppression on a multi-line statement's opening line
        # covers findings anchored to its continuation lines
        self.stmt_start = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.stmt) and node.end_lineno is not None:
                for ln in range(node.lineno, node.end_lineno + 1):
                    if node.lineno > self.stmt_start.get(ln, 0):
                        self.stmt_start[ln] = node.lineno

    def _scan_comments(self):
        try:
            tokens = tokenize.generate_tokens(io.StringIO(self.source).readline)
            comments = [
                (t.start[0], t.string) for t in tokens
                if t.type == tokenize.COMMENT
            ]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            comments = [
                (i + 1, line[line.index("#"):])
                for i, line in enumerate(self.lines) if "#" in line
            ]
        directive_re = _directive_re(self.tool)
        for lineno, text in comments:
            m = directive_re.search(text)
            if m:
                kind, raw_rules, just = m.group(1), m.group(2), m.group(3) or ""
                rules = {r.strip() for r in raw_rules.split(",") if r.strip()}
                if kind == "disable":
                    self.suppress_line[lineno] = (rules, just)
                elif kind == "disable-next":
                    target, just = self._next_code_line(lineno, just)
                    self.suppress_next[target - 1] = (rules, just)
                else:  # disable-file
                    for r in rules:
                        self.suppress_file[r] = just
            for marker_re in _marker_res():
                m = marker_re.search(text)
                if m:
                    self.markers.setdefault(lineno, set()).add(m.group(1))

    def _next_code_line(self, lineno, justification):
        """A ``disable-next`` applies to the first CODE line after it —
        justifications may wrap over several comment lines, which are
        folded into the justification text."""
        t = lineno + 1
        while t <= len(self.lines):
            stripped = self.lines[t - 1].strip()
            if stripped and not stripped.startswith("#"):
                break
            if stripped.startswith("#") and not _directive_re(
                self.tool
            ).search(stripped):
                justification = (
                    justification + " " + stripped.lstrip("# ").strip()
                ).strip()
            t += 1
        return t, justification

    def ancestors(self, node):
        cur = self.parents.get(node)
        while cur is not None:
            yield cur
            cur = self.parents.get(cur)

    def enclosing_function(self, node):
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    def function_markers(self, node):
        """Markers on the ``def`` line or the line directly above it."""
        out = set()
        for ln in (node.lineno, node.lineno - 1):
            out |= self.markers.get(ln, set())
        return out

    def suppression_for(self, rule_name, rule_id, line):
        """(suppressed, justification) for a finding at ``line``. A
        suppression matches on the finding's own line or on the opening
        line of the (multi-line) statement containing it."""
        if rule_name in self.suppress_file:
            return True, self.suppress_file[rule_name]
        if rule_id in self.suppress_file:
            return True, self.suppress_file[rule_id]
        candidates = {line, self.stmt_start.get(line, line)}
        for ln in candidates:
            entry = self.suppress_line.get(ln)
            if entry and (rule_name in entry[0] or rule_id in entry[0]):
                return True, entry[1]
            entry = self.suppress_next.get(ln - 1)
            if entry and (rule_name in entry[0] or rule_id in entry[0]):
                return True, entry[1]
        return False, ""


@dataclasses.dataclass
class LintResult:
    findings: list
    files_scanned: int

    @property
    def unsuppressed(self):
        return [f for f in self.findings if not f.suppressed]

    @property
    def suppressed(self):
        return [f for f in self.findings if f.suppressed]


class LintContext:
    """Shared, lazily-computed project state handed to every rule."""

    def __init__(self, index, config):
        self.index = index
        self.config = config
        self._hot = None

    @property
    def hot_functions(self):
        if self._hot is None:
            from pyrecover_tpu.analysis.callgraph import build_hot_set

            self._hot = build_hot_set(self.index, self.config)
        return self._hot


def _iter_py_files(paths):
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" in f.parts:
                    continue
                yield f
        elif p.suffix == ".py":
            yield p


def _load_modules(paths, tool="jaxlint", error_id="JX00"):
    modules, findings = [], []
    for f in _iter_py_files(paths):
        try:
            source = f.read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError) as e:
            findings.append(Finding(
                rule="unreadable-file", rule_id=error_id, severity="error",
                path=str(f), line=1, col=1, message=f"cannot read file: {e}",
            ))
            continue
        try:
            rel = f.resolve().relative_to(Path.cwd())
        except ValueError:
            rel = f
        try:
            modules.append(ModuleInfo(f, source, relpath=rel, tool=tool))
        except SyntaxError as e:
            findings.append(Finding(
                rule="syntax-error", rule_id=error_id, severity="error",
                path=str(rel), line=e.lineno or 1, col=(e.offset or 1),
                message=f"syntax error: {e.msg}",
            ))
    return modules, findings


def run_rules(modules, config=None):
    """Run every enabled rule over the parsed modules; returns findings
    with suppressions resolved."""
    from pyrecover_tpu.analysis.callgraph import ProjectIndex
    from pyrecover_tpu.analysis.rules import RULES

    config = config or DEFAULT_CONFIG
    index = ProjectIndex(modules)
    ctx = LintContext(index, config)
    findings = []
    for module in modules:
        for rule in RULES.values():
            if not config.rule_enabled(rule.name, rule.id):
                continue
            for f in rule.check(module, ctx):
                f.suppressed, f.justification = module.suppression_for(
                    f.rule, f.rule_id, f.line
                )
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule_id))
    return findings


def lint_paths(paths, config=None):
    modules, pre = _load_modules(paths)
    findings = pre + run_rules(modules, config)
    return LintResult(findings=findings, files_scanned=len(modules) + len(pre))


def lint_source(source, name="<snippet>", config=None):
    """Lint one in-memory source string (the fixture-test entry point)."""
    module = ModuleInfo(name, source, relpath=name)
    return LintResult(findings=run_rules([module], config), files_scanned=1)
