"""FT-JIT: retrace / host-sync hazards inside jitted code.

``core/jax_engine.py`` builds its kernels as closures decorated with
``functools.partial(jax.jit, static_argnames=(...))``.  Inside such a
function (and anything it calls), four Python idioms silently destroy
the performance contract:

* ``for``/``while`` over a traced value — unrolls per element or fails;
* ``if`` on a traced value — a ``TracerBoolConversionError`` at best, a
  retrace-per-value loop at worst (the ``departure_fill`` trap PR 9
  documented: shape-shrinking Python loops retrace every iteration);
* ``float()`` / ``bool()`` / ``int()`` / ``.item()`` / ``.tolist()`` on
  a traced array — a device->host sync in the middle of the kernel;
* ``np.*`` calls on traced arrays — a silent host round-trip (numpy
  forces concretization) that turns the fused pipeline into ping-pong;
* ``span(...)`` / ``count(...)`` of ``core/spans.py`` — the Python body
  runs once, at trace time, so the span records nothing per call.

The rule runs a small interprocedural taint analysis: parameters of a
jit entry that are NOT in ``static_argnames`` are traced; taint
propagates through assignments and through calls to same-module
helpers (per-call-site, so ``_hash_grid_j(fields, dev_seed,
hash_backend)`` taints the arrays but not the static backend string).
Known-static accesses never carry taint: ``x.shape`` / ``x.ndim`` /
``x.dtype`` / ``x.size`` / ``len(x)`` are trace-time constants, and
``x is None`` / ``x is not None`` is Python-level structure dispatch,
not a value branch — so the codebase's ``for f in
range(fields.shape[1])`` and ``if cell_salt is not None`` idioms stay
clean by construction.

Functions *defined inside* a jit entry (``cond``/``body`` closures
handed to ``lax.while_loop``) are analyzed with all their parameters
traced plus the enclosing taint, since their arguments are loop-carried
tracers by construction.
"""

from __future__ import annotations

import ast

from ..common import Context, Finding, SourceFile, call_name

RULE_LOOP = "FT-JIT-LOOP"
RULE_BRANCH = "FT-JIT-BRANCH"
RULE_HOSTSYNC = "FT-JIT-HOSTSYNC"
RULE_NUMPY = "FT-JIT-NUMPY"
RULE_SPAN = "FT-JIT-SPAN"
RULE_IDS = (RULE_LOOP, RULE_BRANCH, RULE_HOSTSYNC, RULE_NUMPY, RULE_SPAN)

#: Modules that contain (or build) jitted kernels.
JIT_MODULES = (
    "src/repro/core/jax_engine.py",
    "src/repro/core/strategies.py",
)

#: Attribute accesses on a traced array that are static at trace time.
STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

#: Builtins whose call on a traced value forces a host sync.
HOST_CASTS = {"float", "bool", "int", "complex"}

#: Method calls on a traced value that force a host sync.
HOST_METHODS = {"item", "tolist", "numpy"}

NUMPY_ALIASES = {"np", "numpy"}

#: Host spans and counters (``core/spans.py``): trace-time only in jit.
SPAN_CALLS = {"span", "count", "spans.span", "spans.count"}


def _is_jax_jit_expr(node: ast.expr) -> bool:
    """True for ``jax.jit`` / ``jit`` expressions."""
    if isinstance(node, ast.Attribute):
        return (node.attr == "jit" and isinstance(node.value, ast.Name)
                and node.value.id == "jax")
    return isinstance(node, ast.Name) and node.id == "jit"


def jit_static_argnames(fn: ast.FunctionDef) -> tuple[bool, set[str]]:
    """(is jit entry, static_argnames) from the decorator list.

    Recognized shapes: ``@jax.jit``, ``@jit``,
    ``@jax.jit(static_argnames=...)``, and
    ``@[functools.]partial(jax.jit, static_argnames=...)``.
    """
    for dec in fn.decorator_list:
        if _is_jax_jit_expr(dec):
            return True, set()
        if not isinstance(dec, ast.Call):
            continue
        callee = call_name(dec)
        if _is_jax_jit_expr(dec.func):
            return True, _static_names(dec)
        if callee in ("functools.partial", "partial") and dec.args \
                and _is_jax_jit_expr(dec.args[0]):
            return True, _static_names(dec)
    return False, set()


def _static_names(call: ast.Call) -> set[str]:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                return {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return set()


def _param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


class _TaintChecker(ast.NodeVisitor):
    """Taint-aware hazard scan of one function body under a given set of
    traced names.  Collects findings and the call sites into same-module
    helpers (with per-argument taint) for the interprocedural worklist."""

    def __init__(self, sf: SourceFile, fn: ast.FunctionDef,
                 tainted: set[str], local_funcs: dict[str, ast.FunctionDef],
                 qualname: str):
        self.sf = sf
        self.fn = fn
        self.tainted = set(tainted)
        self.local_funcs = local_funcs
        self.qualname = qualname
        self.findings: list[Finding] = []
        self.helper_calls: list[tuple[str, frozenset[str]]] = []
        self.nested: list[ast.FunctionDef] = []

    # -- taint query ------------------------------------------------------

    def expr_taint(self, node: ast.expr | None) -> bool:
        """Does evaluating ``node`` observe a traced *value*?  Accesses
        that are static at trace time (shape/ndim/dtype/size, len(),
        ``is [not] None``) do not count."""
        if node is None:
            return False
        for sub, parents in _walk_with_parents(node):
            if not isinstance(sub, ast.Name) or sub.id not in self.tainted:
                continue
            if not self._static_context(sub, parents):
                return True
        return False

    def _static_context(self, name: ast.Name,
                        parents: tuple[ast.AST, ...]) -> bool:
        """Is this tainted-name use wrapped in a static accessor?"""
        for p in reversed(parents):
            if isinstance(p, ast.Attribute) and p.attr in STATIC_ATTRS:
                return True
            if isinstance(p, ast.Call) and isinstance(p.func, ast.Name) \
                    and p.func.id == "len":
                return True
            if isinstance(p, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in p.ops):
                # `x is None` / `x is not None`: structure, not value
                return True
        return False

    # -- statements -------------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef):
        if node is not self.fn:
            self.nested.append(node)   # analyzed with full-taint params
            return
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node: ast.Assign):
        self._assign(node.targets, node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._assign([node.target], node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        if node.value is not None:
            self._assign([node.target], node.value)
        self.generic_visit(node)

    def _assign(self, targets: list[ast.expr], value: ast.expr):
        if self.expr_taint(value):
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        self.tainted.add(sub.id)

    def visit_For(self, node: ast.For):
        if self.expr_taint(node.iter):
            self._emit(RULE_LOOP, node,
                       f"Python `for` over traced value in jitted "
                       f"`{self.qualname}` (iterating "
                       f"`{_snippet(node.iter)}` unrolls per element "
                       f"or retraces)",
                       "hoist to lax.fori_loop/scan, or iterate a static "
                       "shape: `for i in range(x.shape[k])`")
        else:
            # loop targets over a static iterable stay untainted
            pass
        if self.expr_taint(node.iter):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    self.tainted.add(sub.id)
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        if self.expr_taint(node.test):
            self._emit(RULE_LOOP, node,
                       f"Python `while` on traced value in jitted "
                       f"`{self.qualname}` (test `{_snippet(node.test)}`)",
                       "use lax.while_loop with the condition inside the "
                       "traced cond function")
        self.generic_visit(node)

    def visit_If(self, node: ast.If):
        if self.expr_taint(node.test):
            self._emit(RULE_BRANCH, node,
                       f"Python `if` on traced value in jitted "
                       f"`{self.qualname}` (test `{_snippet(node.test)}`)",
                       "branch with jnp.where/lax.cond, or make the "
                       "operand a static_argname")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp):
        if self.expr_taint(node.test):
            self._emit(RULE_BRANCH, node,
                       f"conditional expression on traced value in jitted "
                       f"`{self.qualname}` (test `{_snippet(node.test)}`)",
                       "use jnp.where instead of `a if t else b`")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        callee = call_name(node)
        args_taint = any(self.expr_taint(a) for a in node.args) or any(
            self.expr_taint(kw.value) for kw in node.keywords)
        if callee in HOST_CASTS and args_taint:
            self._emit(RULE_HOSTSYNC, node,
                       f"`{callee}()` on traced value in jitted "
                       f"`{self.qualname}` forces a device->host sync",
                       "keep the value traced (jnp ops) or mark the "
                       "argument static")
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in HOST_METHODS \
                and self.expr_taint(node.func.value):
            self._emit(RULE_HOSTSYNC, node,
                       f"`.{node.func.attr}()` on traced value in jitted "
                       f"`{self.qualname}` forces a device->host sync",
                       "return the traced array and materialize outside "
                       "the jit boundary")
        elif callee.partition(".")[0] in NUMPY_ALIASES and args_taint:
            rule = (RULE_HOSTSYNC
                    if callee.split(".")[-1] in ("asarray", "array")
                    else RULE_NUMPY)
            self._emit(rule, node,
                       f"`{callee}` called on traced value in jitted "
                       f"`{self.qualname}` (numpy concretizes the tracer)",
                       "use the jnp twin of the operation inside jit")
        elif callee in SPAN_CALLS:
            self._emit(RULE_SPAN, node,
                       f"`{callee}()` in jitted `{self.qualname}` runs "
                       f"once at trace time and records nothing per call",
                       "open the span around the jitted call, on the host")
        elif callee in self.local_funcs and callee != self.qualname:
            taint = frozenset(self._callsite_taint(node, callee))
            self.helper_calls.append((callee, taint))
        self.generic_visit(node)

    def _callsite_taint(self, node: ast.Call, callee: str) -> set[str]:
        params = _param_names(self.local_funcs[callee])
        out: set[str] = set()
        for i, arg in enumerate(node.args):
            if i < len(params) and self.expr_taint(arg):
                out.add(params[i])
        for kw in node.keywords:
            if kw.arg in params and self.expr_taint(kw.value):
                out.add(kw.arg)
        return out

    def _emit(self, rule: str, node: ast.AST, message: str, hint: str):
        self.findings.append(Finding(
            rule=rule, file=self.sf.rel,
            line=getattr(node, "lineno", 1), message=message, hint=hint))


def _walk_with_parents(node: ast.AST):
    stack: list[tuple[ast.AST, tuple[ast.AST, ...]]] = [(node, ())]
    while stack:
        cur, parents = stack.pop()
        yield cur, parents
        for child in ast.iter_child_nodes(cur):
            stack.append((child, parents + (cur,)))


def _collect_functions(tree: ast.Module) -> dict[str, ast.FunctionDef]:
    """name -> FunctionDef for every function in the module (nested
    included; inner names shadow outer on collision, which matches the
    call-by-bare-name resolution the checker does)."""
    out: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, node)
    return out


def _analyze_module(sf: SourceFile) -> list[Finding]:
    funcs = _collect_functions(sf.tree)
    findings: list[Finding] = []
    # worklist of (function name, tainted params); analyzing a function
    # under a superset of any earlier taint set supersedes that run, so
    # track the union seen per function and re-run only on growth
    seen: dict[str, set[str]] = {}
    work: list[tuple[ast.FunctionDef, set[str], str]] = []

    for name, fn in funcs.items():
        is_jit, static = jit_static_argnames(fn)
        if is_jit:
            tainted = {p for p in _param_names(fn) if p not in static}
            work.append((fn, tainted, name))
            seen[name] = set(tainted)

    emitted: set[tuple[str, str, int]] = set()
    budget = 200   # hard cap: the worklist is tiny in practice
    while work and budget:
        budget -= 1
        fn, tainted, qualname = work.pop()
        checker = _TaintChecker(sf, fn, tainted, funcs, qualname)
        checker.visit(fn)
        for f in checker.findings:
            key = (f.rule, f.message, f.line)
            if key not in emitted:
                emitted.add(key)
                findings.append(f)
        # closures defined inside jitted code: arguments are tracers by
        # construction (lax.while_loop carries), so all params taint,
        # plus whatever of the enclosing scope they close over
        for nested in checker.nested:
            n_taint = set(_param_names(nested)) | checker.tainted
            prev = seen.get(f"{qualname}.{nested.name}", set())
            if not n_taint <= prev:
                seen[f"{qualname}.{nested.name}"] = prev | n_taint
                work.append((nested, n_taint,
                             f"{qualname}.{nested.name}"))
        # same-module helpers: taint flows per call site
        for callee, taint in checker.helper_calls:
            prev = seen.get(callee, set())
            if not set(taint) <= prev:
                seen[callee] = prev | set(taint)
                work.append((funcs[callee], prev | set(taint), callee))
    return findings


def _snippet(node: ast.expr) -> str:
    try:
        text = ast.unparse(node)
    except Exception:
        return "<expr>"
    return text if len(text) <= 48 else text[:45] + "..."


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    for rel in JIT_MODULES:
        sf = ctx.source(rel)
        if sf is not None:
            findings.extend(_analyze_module(sf))
    return findings
