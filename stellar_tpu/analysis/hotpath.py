"""Hot-path sync/retrace lint: AST pass over jit-adjacent code.

Two hazard families the verify hot path must stay free of:

* **host-sync hazards** (device files, ``stellar_tpu/ops/``): forcing a
  traced value to the host inside code that runs under ``jit`` —
  ``np.asarray``/``np.array`` on a traced value, ``.item()``,
  ``.tolist()``, ``.block_until_ready()``, ``float()/int()/bool()`` of a
  traced value, and Python control flow (``if``/``while``/``for
  range()``/``assert``) branching on traced data. Any of these either
  fails at trace time or, worse, silently splits the kernel into
  multiple dispatches with a device round-trip between them — the
  exact latency class PR 2's dispatch work is fighting.
* **retrace hazards** (device + dispatch files): building a fresh
  ``jax.jit`` wrapper inside a function body. Each wrapper carries its
  own trace cache, so a per-call wrapper recompiles every call; a
  jitted local closure additionally captures enclosing locals by value
  (shape-carrying or non-hashable captures poison the cache key).

Taint model: function parameters are traced-unknown unless they carry a
non-tensor default (``need_t=True``-style static config, part of the jit
cache key); names assigned from tainted expressions become tainted;
shape-carrying accessors (``.ndim``/``.shape``/``.dtype``/``.size``,
``len()``, ``is None``, ``isinstance``) launder taint — branching on
shapes is trace-time-static and safe.

Findings are filtered through the reviewed allowlist below; every entry
carries a written safety argument (see ``docs/static_analysis.md``).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from stellar_tpu.analysis.lint_base import (
    Allowlist, Finding, LintReport, finish_report, repo_root, walk_py,
)

__all__ = ["run", "lint_source", "SCOPE_DEVICE", "SCOPE_HOST",
           "ALLOWLIST"]

# Files whose function bodies are (or feed) traced device code.
SCOPE_DEVICE = ["stellar_tpu/ops"]
# Host-side dispatch code: retrace rules only. Since ISSUE 7 the
# dispatch loop (and both jit wrapper sites) lives in the generic
# batch engine; the verifier and hasher are thin plugin modules.
SCOPE_HOST = [
    "stellar_tpu/crypto/batch_verifier.py",
    "stellar_tpu/crypto/batch_hasher.py",
    "stellar_tpu/parallel/batch_engine.py",
]

_SYNC_NP_FUNCS = {"asarray", "array"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_SYNC_BUILTINS = {"float", "int", "bool"}
_SHAPEISH_ATTRS = {"ndim", "shape", "dtype", "size"}
_LAUNDER_CALLS = {"len", "isinstance", "hasattr", "getattr", "type"}

# file -> {finding-key: written safety argument}
ALLOWLIST = Allowlist({
    "stellar_tpu/ops/field25519.py": {
        "traced-branch:_pow2k.k":
            "k is a compile-time Python int at every call site (the "
            "literal exponents of the inversion addition chain); the "
            "branch picks unroll-vs-fori_loop at trace time and k is "
            "part of the jit cache key, so no data-dependent control "
            "flow or retrace can occur.",
        "host-sync:from_int.np.array":
            "documented host-side helper: builds a constant limb "
            "vector from a Python int at import/trace time; it is "
            "never called on a traced value (callers pass module "
            "constants or host ints).",
        "host-sync:to_int.np.asarray":
            "documented host-side test helper (docstring says so); "
            "callers are tests and host oracles comparing device "
            "output AFTER an explicit fetch, never traced code.",
    },
    "stellar_tpu/ops/edwards.py": {
        "traced-branch:_unstack_points.n":
            "n is a static Python int (the stack width, always a "
            "literal at call sites) — trace-time unrolling of a "
            "fixed-size tuple, not data-dependent control flow.",
    },
    "stellar_tpu/ops/verify.py": {
        "jit-in-func:verify_kernel_sharded.jax.jit":
            "the wrapper is constructed once per mesh by its callers "
            "(the __graft_entry__ dryrun harness; production dispatch "
            "is per-device sub-chunks of the plain kernel); it never "
            "runs per-dispatch, so there is exactly one trace per "
            "(mesh, bucket) pair.",
    },
    "stellar_tpu/ops/sha256.py": {
        "traced-branch:pack_messages.max_blocks":
            "host-side packing helper (docstring says so): operates "
            "on Python bytes before any device dispatch — max_blocks "
            "is a static Python int (the plugin's block capacity) and "
            "the per-message loop runs over host bytes, never traced "
            "values.",
        "host-sync:digest_words_to_bytes.np.asarray":
            "documented host-side decoder: renders a digest row AFTER "
            "the engine's explicit fetch (callers hold numpy arrays, "
            "never tracers) — the np.asarray is a dtype-cast of host "
            "memory, not a device sync.",
    },
    "stellar_tpu/parallel/batch_engine.py": {
        "jit-in-func:_kernel_for.jax.jit":
            "built once per dispatch shape and memoized in "
            "self._kernels under its lock — the per-call path is a "
            "dict hit, no fresh wrapper and no retrace.",
        "jit-in-func:probe.jax.jit":
            "intentional: each breaker-paced probe must prove the "
            "FULL device path including compile+dispatch (a cached "
            "wrapper "
            "could vacuously re-close a dispatch-opened breaker); "
            "probes are exponential-backoff-paced, so the recompile "
            "cost is bounded by design.",
    },
})


def _is_shapeish(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute)
            and node.attr in _SHAPEISH_ATTRS)


class _FuncLinter:
    """Intraprocedural taint pass over one function body."""

    def __init__(self, fname: str, rel: str, device_file: bool,
                 findings: List[Finding]):
        self.fname = fname
        self.rel = rel
        self.device = device_file
        self.findings = findings
        self.taint: Set[str] = set()

    # --- taint of an expression ---

    def _expr_tainted(self, node: ast.AST) -> bool:
        if node is None or isinstance(node, (ast.Constant,)):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.taint
        if _is_shapeish(node):
            return False  # shapes are static under trace
        if isinstance(node, ast.Attribute):
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id in _LAUNDER_CALLS:
                return False
            parts = [fn] + list(node.args) + \
                [kw.value for kw in node.keywords]
            return any(self._expr_tainted(p) for p in parts)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                return False  # `x is None` guards are structural
            return any(self._expr_tainted(c)
                       for c in [node.left] + list(node.comparators))
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.expr, ast.comprehension)):
                if self._expr_tainted(child):
                    return True
        return False

    # --- rules ---

    def _emit(self, node: ast.AST, rule: str, symbol: str, msg: str):
        self.findings.append(Finding(
            file=self.rel, line=getattr(node, "lineno", 0), rule=rule,
            symbol=symbol, message=msg))

    def _check_sync_call(self, node: ast.Call):
        fn = node.func
        args_tainted = any(self._expr_tainted(a) for a in node.args)
        if isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and fn.value.id == "np" \
                and fn.attr in _SYNC_NP_FUNCS and args_tainted:
            self._emit(node, "host-sync",
                       f"{self.fname}.np.{fn.attr}",
                       f"np.{fn.attr} on a traced value forces a "
                       "host sync / concretization inside jitted code")
        elif isinstance(fn, ast.Attribute) and \
                fn.attr in _SYNC_METHODS and \
                self._expr_tainted(fn.value):
            self._emit(node, "host-sync",
                       f"{self.fname}.{fn.attr}",
                       f".{fn.attr}() on a traced value blocks on "
                       "device transfer")
        elif isinstance(fn, ast.Name) and fn.id in _SYNC_BUILTINS and \
                args_tainted:
            self._emit(node, "host-sync",
                       f"{self.fname}.{fn.id}",
                       f"{fn.id}() of a traced value concretizes at "
                       "trace time (or fails)")

    @staticmethod
    def _is_jit_expr(node: ast.AST) -> bool:
        """jax.jit / bare `jit` (from jax import jit) /
        functools.partial(jax.jit, ...) — anything that builds a fresh
        jit wrapper when evaluated."""
        if isinstance(node, ast.Attribute) and node.attr == "jit" and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "jax":
            return True
        if isinstance(node, ast.Name) and node.id == "jit":
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            is_partial = (
                (isinstance(fn, ast.Attribute) and fn.attr == "partial")
                or (isinstance(fn, ast.Name) and fn.id == "partial"))
            if is_partial:
                return any(_FuncLinter._is_jit_expr(a)
                           for a in node.args)
        return False

    def _emit_jit(self, node: ast.AST, symbol: str, captures: str = ""):
        self._emit(node, "jit-in-func", f"{self.fname}.{symbol}",
                   "jax.jit wrapper built inside a function body: a "
                   "fresh wrapper per call means a fresh trace cache "
                   "per call (recompile every time)" + captures)

    def _check_jit_call(self, node: ast.Call):
        if not self._is_jit_expr(node.func):
            return
        captures = ""
        if node.args and isinstance(node.args[0], ast.Lambda):
            captures = (" (jitted lambda: closure captures become "
                        "part of the trace, shape-carrying or "
                        "non-hashable captures poison the cache)")
        self._emit_jit(node, "jax.jit", captures)

    def _check_jit_decorators(self, fnode) -> None:
        """A nested def decorated with @jax.jit / @jit / @partial(jit)
        builds a fresh wrapper every time the enclosing function runs —
        the decorator spelling of the same retrace hazard."""
        for dec in fnode.decorator_list:
            if self._is_jit_expr(dec):
                self._emit_jit(
                    dec, f"{fnode.name}.jax.jit",
                    " (decorated nested def: its closure captures "
                    "become part of the trace)")

    def run(self, fnode: ast.FunctionDef):
        # parameters without a static (non-tensor literal) default are
        # traced-unknown
        args = fnode.args
        all_args = (args.posonlyargs + args.args + args.kwonlyargs)
        defaults = ([None] * (len(args.posonlyargs) + len(args.args)
                              - len(args.defaults))
                    + list(args.defaults) + list(args.kw_defaults))
        for a, d in zip(all_args, defaults):
            if a.arg in ("self", "cls"):
                continue
            if d is not None and isinstance(d, ast.Constant):
                continue  # static config default: part of the cache key
            if d is not None and isinstance(d, ast.Tuple) and \
                    all(isinstance(e, ast.Constant) for e in d.elts):
                continue
            self.taint.add(a.arg)
        if args.vararg:
            self.taint.add(args.vararg.arg)
        if args.kwarg:
            self.taint.add(args.kwarg.arg)

        # two forward passes so loop-carried taint converges
        for _ in range(2):
            for node in self._walk_own(fnode):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    value = getattr(node, "value", None)
                    if value is None or not self._expr_tainted(value):
                        continue
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    for t in targets:
                        self._taint_target(t)
                elif isinstance(node, ast.For):
                    if self._expr_tainted(node.iter):
                        self._taint_target(node.target)

        for node in self._walk_own(fnode):
            if isinstance(node, ast.Call):
                if self.device:
                    self._check_sync_call(node)
                self._check_jit_call(node)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                # nested def: its body is the nested linter's scope,
                # but ITS decorators evaluate in THIS scope, per call
                self._check_jit_decorators(node)
            elif self.device and isinstance(node,
                                            (ast.If, ast.While)):
                if self._expr_tainted(node.test):
                    sym = self._cond_symbol(node.test)
                    self._emit(
                        node, "traced-branch", f"{self.fname}.{sym}",
                        "Python branch on a traced value inside "
                        "device code: fails at trace time or forces "
                        "a concretizing sync")
            elif self.device and isinstance(node, ast.Assert):
                if self._expr_tainted(node.test):
                    sym = self._cond_symbol(node.test)
                    self._emit(
                        node, "traced-branch", f"{self.fname}.{sym}",
                        "assert on a traced value inside device code")
            elif self.device and isinstance(node, ast.For):
                if self._range_tainted(node.iter):
                    sym = self._cond_symbol(node.iter)
                    self._emit(
                        node, "traced-branch", f"{self.fname}.{sym}",
                        "Python loop with a data-dependent trip count "
                        "(range over a traced value) inside device "
                        "code")
            elif self.device and isinstance(
                    node, (ast.ListComp, ast.SetComp, ast.DictComp,
                           ast.GeneratorExp)):
                for comp in node.generators:
                    if self._range_tainted(comp.iter):
                        sym = self._cond_symbol(comp.iter)
                        self._emit(
                            node, "traced-branch",
                            f"{self.fname}.{sym}",
                            "comprehension with a data-dependent trip "
                            "count (range over a traced value) inside "
                            "device code")

    def _range_tainted(self, it: ast.AST) -> bool:
        """True for ``range(<tainted>)``-shaped iterators: the trip
        count itself is data-dependent. Iterating a tuple/zip of traced
        arrays is static-width unrolling and is NOT flagged."""
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id in ("range", "reversed"):
            return any(self._expr_tainted(a) for a in it.args)
        return False

    @staticmethod
    def _walk_own(fnode: ast.FunctionDef):
        """Walk a function body in SOURCE ORDER without descending into
        nested function definitions (each nested def gets its own
        linter scope). Source order matters: the taint passes are
        forward dataflow — a reversed walk would only propagate taint
        one assignment link per pass."""
        stack = list(reversed(list(ast.iter_child_nodes(fnode))))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                continue
            stack.extend(reversed(list(ast.iter_child_nodes(node))))

    def _taint_target(self, t: ast.AST):
        """Taint assignment-target names: a subscripted target taints
        its base container, never the index expression's names."""
        if isinstance(t, ast.Name):
            self.taint.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._taint_target(e)
        elif isinstance(t, (ast.Subscript, ast.Attribute, ast.Starred)):
            base = t.value if not isinstance(t, ast.Starred) else t.value
            if isinstance(t, ast.Starred):
                self._taint_target(t.value)
            else:
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                if isinstance(base, ast.Name):
                    self.taint.add(base.id)

    def _cond_symbol(self, node: ast.AST) -> str:
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in self.taint:
                return n.id
        return "<expr>"


def _lint_tree(tree: ast.Module, rel: str, device_file: bool,
               findings: List[Finding]):
    # lint every function (including nested defs, each with its own
    # taint scope; nested functions inherit nothing — conservative for
    # closures, which is fine: closure reads of traced locals surface
    # at their own call sites)
    def visit(node, prefix=""):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                linter = _FuncLinter(child.name, rel, device_file,
                                     findings)
                linter.run(child)
                visit(child, prefix + child.name + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, prefix + child.name + ".")
            else:
                visit(child, prefix)
    visit(tree)


def lint_source(src: str, rel: str,
                device_file: bool = True) -> List[Finding]:
    """Lint one source text (unit-test hook)."""
    findings: List[Finding] = []
    _lint_tree(ast.parse(src), rel, device_file, findings)
    return findings


def run(allowlist: Optional[Allowlist] = None) -> LintReport:
    allowlist = allowlist or ALLOWLIST
    root = repo_root()
    findings: List[Finding] = []
    files = 0
    for paths, device in ((SCOPE_DEVICE, True), (SCOPE_HOST, False)):
        for path in walk_py(paths, root):
            rel = str(path.relative_to(root))
            files += 1
            _lint_tree(ast.parse(path.read_text()), rel, device,
                       findings)
    return finish_report("hotpath", files, findings, allowlist)
