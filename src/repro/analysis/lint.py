"""The repro AST linter — repo conventions as machine-checked invariants.

``python -m repro.lint [paths]`` parses every ``.py`` file under the given
paths (default ``src``) and reports ``file:line rule message`` findings,
exiting nonzero when any survive.  Rules live in an open registry —
:func:`register_rule` mirrors ``repro.core.execplan.register_backend`` —
so a plugin (or a test) can add a rule without touching this module.

Builtin rules:

  * ``compat-drift`` — drift-prone JAX symbols (``shard_map``,
    ``segment_sum``, ``enable_x64``, ``axis_size``) and direct
    ``.cost_analysis()`` calls must go through ``repro.compat`` (the
    ROADMAP compat policy); ``jax.experimental.pallas`` / ``pltpu``
    imports are allowlisted inside ``kernels/``.
  * ``x64-leak`` — a global ``jax.config.update("jax_enable_x64", ...)``
    outside the compat scoped context manager flips precision for the
    whole process (the sweep's parity pins depend on scoped x64).
  * ``donation-misuse`` — a name donated via ``donate_argnums`` /
    ``donate_argnames`` is read again after the jitted call in the same
    scope (the PR 3 donated-buffer bug class: donation deletes the
    caller's buffer).
  * ``jit-in-loop`` — constructing ``jax.jit(...)`` / ``pl.pallas_call``
    inside a ``for``/``while`` body defeats the jit cache (retrace +
    recompile every iteration).
  * ``host-sync-in-jit`` — ``np.asarray`` / ``.item()`` / ``float()``
    applied to traced values inside a jit-decorated or jit-wrapped
    function forces a host sync (and fails under ``jit`` at trace time).

Suppression: append ``# repro: noqa`` (all rules) or
``# repro: noqa[rule-a,rule-b]`` to the offending line.  Rules may also
carry path allowlists (``register_rule(..., allow_paths=(...,))``,
fnmatch patterns against the reported path) — e.g. ``compat-drift`` is
allowlisted for ``repro/compat.py`` itself, the ONE place drift imports
belong.

Everything here is stdlib-only: the linter runs without jax installed.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import fnmatch
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable, Iterator

# --------------------------------------------------------------------------
# Findings, file context, rule registry
# --------------------------------------------------------------------------


@dataclass(frozen=True, order=True)
class Finding:
    """One lint violation, printed as ``path:line rule message``."""

    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"


@dataclass
class FileContext:
    """Everything a rule needs about one parsed file."""

    path: Path
    rel: str                     # the path as reported (posix separators)
    tree: ast.Module
    lines: list
    _parents: dict = field(default_factory=dict, repr=False)

    @property
    def parents(self) -> dict:
        """Lazily-built ``{child node: parent node}`` map over the tree."""
        if not self._parents:
            for parent in ast.walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self._parents[child] = parent
        return self._parents


#: A rule check: ``fn(ctx) -> iterable of (node_or_lineno, message)``.
RuleCheck = Callable[[FileContext], Iterable]


@dataclass(frozen=True)
class Rule:
    name: str
    doc: str
    check: RuleCheck
    allow_paths: tuple = ()

    def applies_to(self, rel: str) -> bool:
        return not any(fnmatch.fnmatch(rel, pat) for pat in self.allow_paths)


_RULES: dict = {}


def register_rule(name: str, *, allow_paths=(), overwrite: bool = False):
    """Register a lint rule under ``name`` (decorator).

    The decorated function receives a :class:`FileContext` and yields
    ``(node_or_lineno, message)`` pairs; the engine stamps them into
    :class:`Finding`\\ s.  ``allow_paths`` are fnmatch patterns (matched
    against the reported path) for which the rule is skipped entirely.
    Registering an existing name raises unless ``overwrite=True`` — the
    same contract as ``repro.core.execplan.register_backend``.
    """
    def deco(fn: RuleCheck) -> RuleCheck:
        if not overwrite and name in _RULES:
            raise ValueError(f"lint rule {name!r} is already registered "
                             "(pass overwrite=True to replace it)")
        doc = (fn.__doc__ or "").strip().splitlines()
        _RULES[name] = Rule(name, doc[0] if doc else "", fn,
                            tuple(allow_paths))
        return fn
    return deco


def known_rules() -> tuple:
    """Sorted names of every registered lint rule."""
    return tuple(sorted(_RULES))


# --------------------------------------------------------------------------
# AST helpers shared by the rules
# --------------------------------------------------------------------------

def _dotted(node) -> str:
    """``a.b.c`` for a Name/Attribute chain, '' when it is anything else."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                ast.ClassDef)

#: Spellings that construct a jitted callable.
_JIT_NAMES = frozenset({"jax.jit", "jit", "pjit", "jax.pjit"})


def _scopes(tree: ast.Module) -> Iterator:
    """The module plus every (possibly nested) function definition."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _walk_scope(scope) -> Iterator:
    """All nodes of one scope's body, not descending into nested scopes."""
    stack = list(scope.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, _SCOPE_NODES):
                stack.append(child)


def _jit_construction(node):
    """The ``jax.jit(...)`` Call if ``node`` is one, else ``None``."""
    if isinstance(node, ast.Call) and _dotted(node.func) in _JIT_NAMES:
        return node
    return None


def _int_list(node) -> list:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


def _str_list(node) -> list:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _donate_spec(jit_call: ast.Call) -> tuple:
    """``(argnums, argnames)`` donated by a jit construction."""
    nums, names = [], []
    for kw in jit_call.keywords:
        if kw.arg == "donate_argnums":
            nums = _int_list(kw.value)
        elif kw.arg == "donate_argnames":
            names = _str_list(kw.value)
    return nums, names


def _enclosing_stmt(node, parents: dict):
    while node is not None and not isinstance(node, ast.stmt):
        node = parents.get(node)
    return node


def _param_names(fn) -> set:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n not in ("self", "cls")}


# --------------------------------------------------------------------------
# Rule: compat-drift
# --------------------------------------------------------------------------

#: Symbols whose JAX home has moved (or will): import via repro.compat ONLY.
DRIFT_SYMBOLS = frozenset({"shard_map", "segment_sum", "enable_x64",
                           "axis_size"})


def _in_kernels(rel: str) -> bool:
    return "/kernels/" in rel or rel.startswith("kernels/")


def _mesh_allowed(rel: str) -> bool:
    """Mesh construction is confined to the device-layout seam: the compat
    shim (rule-level allowlist) and ``repro/launch/mesh.py``."""
    return fnmatch.fnmatch(rel, "*repro/launch/mesh.py")


_MESH_MSG = ("construct device meshes through repro.compat.make_mesh / "
             "device_mesh_1d or repro.launch.mesh (mesh construction is "
             "confined to those modules: they build Auto axis types, "
             "where jax.make_mesh defaults to Explicit)")


@register_rule("compat-drift", allow_paths=("*repro/compat.py",))
def compat_drift(ctx: FileContext):
    """Drift-prone JAX symbols imported outside ``repro.compat`` — plus
    device-mesh construction outside the ``compat`` / ``launch.mesh``
    seam."""
    kernels = _in_kernels(ctx.rel)
    mesh_ok = _mesh_allowed(ctx.rel)
    # names that resolve to jax.sharding.Mesh in this file (flag only the
    # CONSTRUCTION — a bare `Mesh` import used for annotations is fine)
    mesh_aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) \
                and (node.module or "") == "jax.sharding":
            for alias in node.names:
                if alias.name == "Mesh":
                    mesh_aliases.add(alias.asname or alias.name)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod != "jax" and not mod.startswith("jax."):
                continue
            for alias in node.names:
                if "pallas" in mod or alias.name == "pallas":
                    if not kernels:
                        yield node, ("jax.experimental.pallas is only "
                                     "imported under src/repro/kernels/ "
                                     "(kernel packages own the Pallas "
                                     "surface)")
                elif alias.name in DRIFT_SYMBOLS:
                    yield node, (f"import {alias.name} from repro.compat, "
                                 f"not {mod} (JAX drift policy; see "
                                 "repro/compat.py)")
                elif alias.name == "make_mesh" and not mesh_ok:
                    yield node, _MESH_MSG
                elif mod.rpartition(".")[2] in DRIFT_SYMBOLS:
                    yield node, (f"import from drifting module {mod}: "
                                 "use the repro.compat shim instead")
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if not alias.name.startswith("jax"):
                    continue
                if "pallas" in alias.name and not kernels:
                    yield node, ("jax.experimental.pallas is only imported "
                                 "under src/repro/kernels/")
                elif alias.name.rpartition(".")[2] in DRIFT_SYMBOLS:
                    yield node, (f"import {alias.name} via repro.compat, "
                                 "not directly (JAX drift policy)")
        elif isinstance(node, ast.Attribute) and node.attr in DRIFT_SYMBOLS:
            root = _dotted(node.value)
            if root == "jax" or root.startswith("jax."):
                yield node, (f"use repro.compat.{node.attr}, not "
                             f"{root}.{node.attr} (its location/signature "
                             "drifts across JAX versions)")
        elif isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "cost_analysis":
                yield node, ("call repro.compat.normalize_cost_analysis("
                             "compiled) — raw .cost_analysis() changes "
                             "shape (list vs dict) across JAX versions")
            elif not mesh_ok:
                fn = _dotted(node.func)
                if fn == "jax.make_mesh":
                    yield node, _MESH_MSG
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in mesh_aliases:
                    yield node, _MESH_MSG
                elif fn.endswith(".Mesh") \
                        and (fn.startswith("jax.") or fn == "sharding.Mesh"):
                    yield node, _MESH_MSG


# --------------------------------------------------------------------------
# Rule: x64-leak
# --------------------------------------------------------------------------

@register_rule("x64-leak", allow_paths=("*repro/compat.py",))
def x64_leak(ctx: FileContext):
    """Global x64 flips outside the compat scoped context manager."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        if _dotted(node.func) not in ("jax.config.update", "config.update"):
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and first.value == "jax_enable_x64":
            yield node, ("global jax.config.update('jax_enable_x64', ...) "
                         "leaks precision process-wide; use the scoped "
                         "repro.compat.enable_x64() context manager")


# --------------------------------------------------------------------------
# Rule: donation-misuse
# --------------------------------------------------------------------------

def _scope_name_events(scope) -> list:
    """Sorted ``(lineno, col, id, ctx)`` for every Name in the scope."""
    events = []
    for node in _walk_scope(scope):
        if isinstance(node, ast.Name):
            events.append((node.lineno, node.col_offset, node.id,
                           type(node.ctx).__name__))
    events.sort()
    return events


def _donated_arg_names(invoke: ast.Call, nums, names) -> list:
    """``(name, arg node)`` for donated arguments passed as plain Names."""
    out = []
    for i in nums:
        if 0 <= i < len(invoke.args) and isinstance(invoke.args[i], ast.Name):
            out.append((invoke.args[i].id, invoke.args[i]))
    for kw in invoke.keywords:
        if kw.arg in names and isinstance(kw.value, ast.Name):
            out.append((kw.value.id, kw.value))
    return out


@register_rule("donation-misuse")
def donation_misuse(ctx: FileContext):
    """Donated buffers read after the donating jitted call (PR 3 class)."""
    for scope in _scopes(ctx.tree):
        events = _scope_name_events(scope)
        assigned: dict = {}        # jitted-callable name -> (nums, names)
        calls = sorted((n for n in _walk_scope(scope)
                        if isinstance(n, ast.Call)),
                       key=lambda n: (n.lineno, n.col_offset))
        invokes = []               # (invoke Call, nums, names)
        for call in calls:
            jc = _jit_construction(call)
            if jc is not None:
                nums, names = _donate_spec(jc)
                if not (nums or names):
                    continue
                stmt = _enclosing_stmt(jc, ctx.parents)
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name) \
                        and stmt.value is jc:
                    assigned[stmt.targets[0].id] = (nums, names)
                continue
            inner = call.func if isinstance(call.func, ast.Call) else None
            jc = _jit_construction(inner) if inner is not None else None
            if jc is not None:                 # jax.jit(f, donate=...)(x)
                nums, names = _donate_spec(jc)
                if nums or names:
                    invokes.append((call, nums, names))
            elif isinstance(call.func, ast.Name) \
                    and call.func.id in assigned:
                nums, names = assigned[call.func.id]
                invokes.append((call, nums, names))

        for invoke, nums, names in invokes:
            stmt = _enclosing_stmt(invoke, ctx.parents)
            if stmt is None:
                continue
            rebound = {n.id for n in ast.walk(stmt)
                       if isinstance(n, ast.Name)
                       and isinstance(n.ctx, ast.Store)}
            end = (stmt.end_lineno, stmt.end_col_offset)
            for name, _node in _donated_arg_names(invoke, nums, names):
                if name in rebound:
                    continue       # x = f(x): the donated name is rebound
                nxt = next((e for e in events
                            if e[2] == name and (e[0], e[1]) > end), None)
                if nxt is not None and nxt[3] == "Load":
                    yield nxt[0], (f"{name!r} was donated to the jitted "
                                   f"call on line {invoke.lineno} — its "
                                   "buffer may be deleted; rebind the "
                                   "result or drop the donation")


# --------------------------------------------------------------------------
# Rule: jit-in-loop
# --------------------------------------------------------------------------

def _inside_loop_body(node, parents: dict) -> bool:
    child, parent = node, parents.get(node)
    while parent is not None:
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            return False           # new scope: constructed per call instead
        if isinstance(parent, (ast.For, ast.AsyncFor)) \
                and child is not parent.target and child is not parent.iter:
            return True
        if isinstance(parent, ast.While) and child is not parent.test:
            return True
        child, parent = parent, parents.get(parent)
    return False


@register_rule("jit-in-loop")
def jit_in_loop(ctx: FileContext):
    """jit/pallas_call constructed per loop iteration (cache defeat)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name in _JIT_NAMES or name.rpartition(".")[2] == "pallas_call":
            if _inside_loop_body(node, ctx.parents):
                yield node, (f"{name}(...) constructed inside a loop body "
                             "retraces/recompiles every iteration — hoist "
                             "the construction out of the loop")


# --------------------------------------------------------------------------
# Rule: host-sync-in-jit
# --------------------------------------------------------------------------

_HOST_FUNCS = frozenset({"np.asarray", "numpy.asarray", "np.array",
                         "numpy.array", "onp.asarray"})
_HOST_CASTS = frozenset({"float", "int", "bool"})


def _is_jit_wrapper(expr) -> bool:
    """True for ``jax.jit`` / ``functools.partial(jax.jit, ...)`` forms."""
    if _dotted(expr) in _JIT_NAMES:
        return True
    if isinstance(expr, ast.Call):
        if _dotted(expr.func) in _JIT_NAMES:
            return True
        if _dotted(expr.func).rpartition(".")[2] == "partial" and expr.args:
            return _is_jit_wrapper(expr.args[0])
    return False


def _wrapped_fn_names(tree: ast.Module) -> set:
    """Names of functions passed (possibly via partial) into jax.jit."""
    out = set()

    def target_name(expr):
        if isinstance(expr, ast.Name):
            out.add(expr.id)
        elif isinstance(expr, ast.Attribute):
            out.add(expr.attr)
        elif isinstance(expr, ast.Call) \
                and _dotted(expr.func).rpartition(".")[2] == "partial" \
                and expr.args:
            target_name(expr.args[0])

    for node in ast.walk(tree):
        jc = _jit_construction(node)
        if jc is not None and jc.args:
            target_name(jc.args[0])
    return out


def _tainted_names(fn, params: set) -> set:
    """Params plus names transitively assigned from them (fixpoint)."""
    tainted = set(params)
    assigns = [n for n in _walk_scope(fn)
               if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))]
    changed = True
    while changed:
        changed = False
        for node in assigns:
            value = node.value
            if value is None:
                continue
            loads = {m.id for m in ast.walk(value)
                     if isinstance(m, ast.Name)
                     and isinstance(m.ctx, ast.Load)}
            if not loads & tainted:
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                for m in ast.walk(t):
                    if isinstance(m, ast.Name) and m.id not in tainted:
                        tainted.add(m.id)
                        changed = True
    return tainted


@register_rule("host-sync-in-jit")
def host_sync_in_jit(ctx: FileContext):
    """Host-sync ops on traced values inside jitted functions."""
    wrapped = _wrapped_fn_names(ctx.tree)
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        decorated = any(_is_jit_wrapper(d) for d in fn.decorator_list)
        if not decorated and fn.name not in wrapped:
            continue
        tainted = _tainted_names(fn, _param_names(fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name in _HOST_FUNCS and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in tainted:
                yield node, (f"{name}() on traced value "
                             f"{node.args[0].id!r} inside jitted "
                             f"{fn.name!r} forces a host sync (fails "
                             "under trace)")
            elif name in _HOST_CASTS and len(node.args) == 1 \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in tainted:
                yield node, (f"{name}() on traced value "
                             f"{node.args[0].id!r} inside jitted "
                             f"{fn.name!r} forces a host sync")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in tainted:
                yield node, (f".item() on traced value "
                             f"{node.func.value.id!r} inside jitted "
                             f"{fn.name!r} forces a host sync")


# --------------------------------------------------------------------------
# Rule: unknown-noqa
# --------------------------------------------------------------------------

_NOQA_RE = re.compile(r"#\s*repro:\s*noqa(?:\[([\w\-,\s]*)\])?")


@register_rule("unknown-noqa")
def unknown_noqa(ctx: FileContext):
    """``# repro: noqa[rule]`` pragmas naming an unregistered rule.

    Only real COMMENT tokens count — a docstring showing the pragma
    syntax as an example is not a pragma.
    """
    import io
    import tokenize
    reader = io.StringIO("\n".join(ctx.lines)).readline
    try:
        comments = [(tok.start[0], tok.string)
                    for tok in tokenize.generate_tokens(reader)
                    if tok.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return
    for lineno, text in comments:
        m = _NOQA_RE.search(text)
        if m is None or m.group(1) is None:
            continue
        for name in sorted({s.strip() for s in m.group(1).split(",")
                            if s.strip()}):
            if name not in _RULES:
                yield lineno, (
                    f"noqa pragma names unregistered rule {name!r} — a "
                    "typo'd pragma suppresses nothing and rots "
                    f"(registered: {', '.join(known_rules())})")


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------


def _suppressed(lines: list, finding: Finding) -> bool:
    if not (0 < finding.line <= len(lines)):
        return False
    m = _NOQA_RE.search(lines[finding.line - 1])
    if not m:
        return False
    if m.group(1) is None:
        return True
    return finding.rule in {s.strip() for s in m.group(1).split(",")
                            if s.strip()}


def _active_rules(select=None) -> list:
    if select is None:
        return [_RULES[n] for n in known_rules()]
    unknown = set(select) - set(_RULES)
    if unknown:
        raise ValueError(f"unknown lint rule(s) {sorted(unknown)} "
                         f"(registered: {', '.join(known_rules())})")
    return [_RULES[n] for n in known_rules() if n in set(select)]


def lint_file(path, rel: str | None = None, select=None) -> list:
    """Lint one file; returns sorted, pragma-filtered :class:`Finding`\\ s."""
    path = Path(path)
    rel = (rel or str(path)).replace("\\", "/")
    source = path.read_text()
    lines = source.splitlines()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 0, "syntax-error", e.msg or "")]
    ctx = FileContext(path=path, rel=rel, tree=tree, lines=lines)
    findings = set()
    for rule in _active_rules(select):
        if not rule.applies_to(rel):
            continue
        for node, message in rule.check(ctx):
            line = node if isinstance(node, int) \
                else getattr(node, "lineno", 0)
            findings.add(Finding(rel, line, rule.name, message))
    return sorted(f for f in findings if not _suppressed(lines, f))


def iter_py_files(paths) -> Iterator:
    """Yield every ``.py`` file under the given files/directories."""
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(p.rglob("*.py"))
        else:
            yield p


def lint_paths(paths, select=None) -> list:
    """Lint files/directories; findings sorted by (path, line, rule)."""
    findings = []
    for f in iter_py_files(paths):
        findings.extend(lint_file(f, select=select))
    return sorted(findings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.lint",
        description="repro AST linter (compat policy, donation, jit and "
                    "x64 hygiene); exits nonzero on findings")
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    ap.add_argument("--select", default=None, metavar="RULES",
                    help="comma-separated rule subset (default: all)")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="findings as text lines (default) or one JSON "
                         "report for CI artifacts")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the registered rules and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name in known_rules():
            print(f"{name:18s} {_RULES[name].doc}")
        return 0

    select = None
    if args.select:
        select = [s.strip() for s in args.select.split(",") if s.strip()]
    try:
        findings = lint_paths(args.paths, select=select)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    n_files = sum(1 for _ in iter_py_files(args.paths))
    if args.format == "json":
        print(json.dumps({"tool": "repro.lint", "n_files": n_files,
                          "n_findings": len(findings),
                          "findings": [dataclasses.asdict(f)
                                       for f in findings]},
                         indent=2))
    else:
        for f in findings:
            print(f)
    status = f"{len(findings)} finding(s) in {n_files} file(s)"
    print(f"repro.lint: {status}", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
