"""Repo-specific AST lint (DESIGN.md §9.4).

Three rules, each encoding a discipline the repo's performance or
durability story depends on:

* ``host-sync``   — inside *hot zones* (the functions listed in
  `HOT_ZONES`: the serve decode/admission path, the engine decode loop,
  the per-leaf pipeline sentinels), flag calls that force a device→host
  sync: `jax.device_get(...)`, `.item()`, `block_until_ready(...)`,
  `np.asarray(...)`/`np.array(...)` of a non-literal, and `float(...)`/
  `int(...)` of a call expression. Streaming a sampled token to a
  callback is a sync by design — such sites carry a pragma; anything
  unannotated is a new stall on the hot path. The observability hooks
  (`obs/trace.py`, `obs/metrics.py`) are hot zones too: they run once
  per token/leaf from inside the decode/solve loops and must stay
  append-only host work.
* ``time-in-jit`` — `time.time()`/`perf_counter()`/`monotonic()` inside
  a function that is jitted (decorated with `jax.jit`/`partial(jax.jit)`
  or passed to `jax.jit(...)`/`guard_jit(...)`, including lambdas).
  Wall-clocking a traced function measures trace time once and then
  nothing, silently.
* ``fsync-before-replace`` — in `ft/` and `ckpt/`, every `os.replace`
  must be lexically preceded, in the same function, by an fsync-ish call
  (a name containing "fsync"). An un-fsynced rename is atomic but not
  durable: the journal's crash-safety ordering (DESIGN.md §7/§8) relies
  on contents being on disk before the rename publishes them.

Intentional sites are annotated ``# comq: allow(<rule>)`` on the same
line or the line above; the pragma names the rule it waives (comma-
separated for several). Findings are (path, line, rule, message) —
`lint_paths` walks a tree, `lint_source` lints a snippet (the tests'
fixture hook).
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Sequence, Set, Tuple

RULES = ("host-sync", "time-in-jit", "fsync-before-replace")

# relpath (under src/repro, "/"-separated) -> qualnames whose bodies are
# decode/solve hot loops: any host sync inside runs once per step/leaf
HOT_ZONES: Dict[str, Tuple[str, ...]] = {
    "serve/runtime.py": ("Runtime.step", "Runtime._admit_one",
                         "Runtime.run", "Runtime._emit",
                         "Runtime._clear_slot", "Runtime._retire"),
    "serve/engine.py": ("Engine.generate_batch",),
    "core/guards.py": ("nonfinite_count", "sanitize_array", "gram_health",
                       "result_ok", "guarded_solve"),
    "core/pipeline.py": ("_results_finite", "_RunCtx.commit",
                         "_finalize_report", "_timed_solve"),
    "dist/calibrate.py": ("sharded_gram", "sharded_batched_gram"),
    # observability hooks run once per token/leaf from inside the zones
    # above — they must stay append-only host work (DESIGN.md §10.3)
    "obs/trace.py": ("Tracer.span", "Tracer.request_event",
                     "Tracer.token_event", "Span.__init__",
                     "Span.__enter__", "Span.__exit__"),
    "obs/metrics.py": ("Counter.inc", "Gauge.set", "Gauge.add",
                       "Histogram.observe"),
}

# dirs (relative to the package root) under the durability rule
DURABLE_DIRS = ("ft", "ckpt")

_TIME_CALLS = {"time", "perf_counter", "monotonic"}
_JIT_ENTRY_NAMES = {"jit", "guard_jit"}

_PRAGMA_RE = re.compile(r"#\s*comq:\s*allow\(([^)]*)\)")


@dataclass(frozen=True)
class LintFinding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _pragmas(src: str) -> Dict[int, Set[str]]:
    """line -> set of waived rules, from `# comq: allow(rule[, rule])`."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _dotted(f: ast.AST) -> str:
    """Dotted-ish name of an expression: 'jax.device_get', 'os.replace',
    'x.item', 'float', ... (tail attributes only; subscripts etc. -> '')."""
    parts: List[str] = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    elif parts:
        parts.append("<expr>")
    else:
        return ""
    return ".".join(reversed(parts))


def _call_name(node: ast.Call) -> str:
    return _dotted(node.func)


def _is_literalish(node: ast.AST) -> bool:
    return isinstance(node, (ast.Constant, ast.List, ast.Tuple, ast.Dict))


# ---------------------------------------------------------------------------
# rule: host-sync (hot zones)
# ---------------------------------------------------------------------------

def _host_sync_reason(call: ast.Call) -> str:
    name = _call_name(call)
    tail = name.rsplit(".", 1)[-1]
    if tail == "device_get":
        return "jax.device_get forces a blocking device->host transfer"
    if tail == "item":
        return ".item() forces a blocking scalar device->host sync"
    if tail == "block_until_ready":
        return ("block_until_ready stalls the host until the device "
                "queue drains")
    if (name in ("np.asarray", "np.array", "numpy.asarray", "numpy.array")
            and call.args and isinstance(call.args[0], ast.Call)):
        # np.asarray(<call result>): pulling a freshly computed device
        # value; a Name/Subscript arg is usually already host data
        return (f"{name}(...) of a device value blocks until the "
                "computation materializes on host")
    if (name in ("float", "int") and call.args
            and isinstance(call.args[0], ast.Call)
            and _call_name(call.args[0]) != "len"):
        return (f"{name}(<call>) pulls a device scalar to host "
                "synchronously")
    return ""


class _FuncIndexer(ast.NodeVisitor):
    """Collects every FunctionDef with its dotted qualname + parents."""

    def __init__(self):
        self.funcs: List[Tuple[str, ast.AST]] = []
        self._stack: List[str] = []

    def _visit_fn(self, node):
        self._stack.append(node.name)
        self.funcs.append((".".join(self._stack), node))
        self.generic_visit(node)
        self._stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_fn(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node)

    def visit_ClassDef(self, node):
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()


def _lint_host_sync(tree: ast.AST, relpath: str) -> List[Tuple[int, str]]:
    zones = HOT_ZONES.get(relpath)
    if not zones:
        return []
    idx = _FuncIndexer()
    idx.visit(tree)
    out: List[Tuple[int, str]] = []
    for qualname, fn in idx.funcs:
        if qualname not in zones:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                reason = _host_sync_reason(node)
                if reason:
                    out.append((node.lineno,
                                f"host sync in hot zone {qualname}: "
                                f"{reason}"))
    return out


# ---------------------------------------------------------------------------
# rule: time-in-jit
# ---------------------------------------------------------------------------

def _jit_callee_names(tree: ast.AST) -> Set[str]:
    """Names of locally-defined functions passed to jit/guard_jit (or
    wrapped via partial(jax.jit, ...) decorators)."""
    jitted: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            tail = _call_name(node).rsplit(".", 1)[-1]
            if tail in _JIT_ENTRY_NAMES:
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        jitted.add(arg.id)
            # partial(jax.jit, ...)(f): the outer call's func is the
            # partial(...) call itself
            if isinstance(node.func, ast.Call):
                inner = node.func
                if (_call_name(inner).rsplit(".", 1)[-1] == "partial"
                        and inner.args
                        and _dotted(inner.args[0]).rsplit(".", 1)[-1]
                        in _JIT_ENTRY_NAMES):
                    for arg in node.args[:1]:
                        if isinstance(arg, ast.Name):
                            jitted.add(arg.id)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                names = []
                if isinstance(dec, ast.Call):
                    names.append(_call_name(dec))
                    names += [_dotted(a) for a in dec.args
                              if isinstance(a, (ast.Attribute, ast.Name))]
                elif isinstance(dec, (ast.Attribute, ast.Name)):
                    names.append(_dotted(dec))
                if any(n.rsplit(".", 1)[-1] in _JIT_ENTRY_NAMES
                       for n in names if n):
                    jitted.add(node.name)
    return jitted


def _jitted_bodies(tree: ast.AST) -> List[ast.AST]:
    """Function/lambda bodies that end up traced by jit."""
    jitted_names = _jit_callee_names(tree)
    bodies: List[ast.AST] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in jitted_names:
                bodies.append(node)
        elif isinstance(node, ast.Call):
            tail = _call_name(node).rsplit(".", 1)[-1]
            if tail in _JIT_ENTRY_NAMES:
                bodies += [a for a in node.args[:1]
                           if isinstance(a, ast.Lambda)]
    return bodies


def _lint_time_in_jit(tree: ast.AST, relpath: str) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for body in _jitted_bodies(tree):
        for node in ast.walk(body):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                head, _, tail = name.rpartition(".")
                if head == "time" and tail in _TIME_CALLS:
                    out.append((node.lineno,
                                f"{name}() inside a jitted function runs "
                                "once at trace time and never again"))
    return out


# ---------------------------------------------------------------------------
# rule: fsync-before-replace (ft/ + ckpt/ durability)
# ---------------------------------------------------------------------------

def _lint_fsync_replace(tree: ast.AST, relpath: str) -> List[Tuple[int, str]]:
    top = relpath.split("/", 1)[0]
    if top not in DURABLE_DIRS:
        return []
    idx = _FuncIndexer()
    idx.visit(tree)
    out: List[Tuple[int, str]] = []
    for qualname, fn in idx.funcs:
        replaces = []
        fsync_lines = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name == "os.replace":
                    replaces.append(node.lineno)
                elif "fsync" in name.rsplit(".", 1)[-1].lower():
                    fsync_lines.append(node.lineno)
        for line in replaces:
            if not any(fl < line for fl in fsync_lines):
                out.append((line,
                            f"os.replace in {qualname} with no preceding "
                            "fsync in the same function — the rename is "
                            "atomic but the contents are not durable"))
    return out


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

_RULE_FNS = {
    "host-sync": _lint_host_sync,
    "time-in-jit": _lint_time_in_jit,
    "fsync-before-replace": _lint_fsync_replace,
}


def lint_source(src: str, relpath: str) -> List[LintFinding]:
    """Lint one file's source. `relpath` is the path under the package
    root ("/"-separated), which selects hot zones and durable dirs."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [LintFinding(relpath, e.lineno or 0, "parse-error", str(e))]
    pragmas = _pragmas(src)

    def waived(line: int, rule: str) -> bool:
        for ln in (line, line - 1):
            if rule in pragmas.get(ln, ()):
                return True
        return False

    findings: List[LintFinding] = []
    for rule, fn in _RULE_FNS.items():
        for line, msg in fn(tree, relpath):
            if not waived(line, rule):
                findings.append(LintFinding(relpath, line, rule, msg))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def _package_relpath(path: str, root: str) -> str:
    rel = os.path.relpath(path, root).replace(os.sep, "/")
    # HOT_ZONES/DURABLE_DIRS are keyed under src/repro; strip the prefix
    for prefix in ("src/repro/", "repro/"):
        if rel.startswith(prefix):
            return rel[len(prefix):]
    return rel


def lint_paths(paths: Sequence[str], root: str = ".") -> List[LintFinding]:
    """Lint every .py file under `paths` (files or directories)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, _, names in os.walk(p):
                files += [os.path.join(dirpath, n) for n in names
                          if n.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
    findings: List[LintFinding] = []
    for f in sorted(files):
        with open(f, encoding="utf-8") as fh:
            src = fh.read()
        rel = _package_relpath(f, root)
        for finding in lint_source(src, rel):
            findings.append(LintFinding(
                os.path.relpath(f, root), finding.line, finding.rule,
                finding.message))
    return findings
