"""FT-DT: dtype drift hazards in the hot-path core modules.

The numpy and jax engines are differential-tested to be bit-identical;
that contract survives only while every array's dtype is pinned where
it is created.  Three construction idioms leave the dtype to the
environment instead:

* ``np.arange(...)`` without ``dtype=`` — numpy's default integer is
  the platform C ``long``: int64 on Linux, int32 on Windows.  An index
  tensor that silently changes width changes overflow behaviour and the
  bit pattern fed to the hash mix.
* ``np.array([...])`` / ``np.asarray([...])`` on a *literal*
  list/tuple/comprehension without ``dtype=`` — the element-derived
  default is platform-int for integer content (same C-long trap) and
  invisible-to-reviewers float64 otherwise.  Arrays built from existing
  arrays preserve their dtype and are not flagged.
* ``jnp.zeros/ones/empty/full/arange/linspace`` without ``dtype=``
  inside the jax engine — jax's default dtype *changes with the x64
  mode* (float32/int32 bare, float64/int64 inside the engine's scoped
  ``jax.enable_x64(True)`` context).  Code that relies on running inside
  that context works, but the dependence is invisible at the call site;
  pin the dtype.

Positional dtypes count (``np.zeros(n, bool)``; ``np.full(shape, v,
np.int32)``), so the codebase's existing pinned calls stay clean.
"""

from __future__ import annotations

import ast

from ..common import Context, Finding, SourceFile, call_name

RULE_ARANGE = "FT-DT-ARANGE"
RULE_LITERAL = "FT-DT-LITERAL"
RULE_JNP = "FT-DT-JNP"
RULE_IDS = (RULE_ARANGE, RULE_LITERAL, RULE_JNP)

#: Hot-path modules under the numpy<->jax bit-identity contract.
HOT_MODULES = (
    "src/repro/core/vector_sim.py",
    "src/repro/core/vector_throughput.py",
    "src/repro/core/strategies.py",
    "src/repro/core/reordering.py",
    "src/repro/core/timeline.py",
    "src/repro/core/jax_engine.py",
    "src/repro/core/compile_fabric.py",
)

#: Modules where jnp constructors are additionally policed (x64-scope
#: dependent defaults).
JNP_MODULES = ("src/repro/core/jax_engine.py",)

NUMPY_ALIASES = ("np", "numpy")
JNP_ALIASES = ("jnp",)

#: func name -> index of the positional dtype slot (None = keyword-only
#: in practice for this rule).
_POSITIONAL_DTYPE_SLOT = {
    "zeros": 1, "ones": 1, "empty": 1, "array": 1, "asarray": 1,
    "full": 2, "linspace": 2,
}

_LITERAL_NODES = (ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp,
                  ast.Set, ast.SetComp)


def _has_dtype(node: ast.Call, fname: str) -> bool:
    if any(kw.arg == "dtype" for kw in node.keywords):
        return True
    slot = _POSITIONAL_DTYPE_SLOT.get(fname)
    return slot is not None and len(node.args) > slot


def _enclosing(parents: tuple[ast.AST, ...]) -> str:
    for p in reversed(parents):
        if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return p.name
    return "<module>"


def _check_module(sf: SourceFile, police_jnp: bool) -> list[Finding]:
    from ..common import iter_parented

    findings: list[Finding] = []
    for node, parents in iter_parented(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = call_name(node)
        alias, _, fname = callee.partition(".")
        if not fname:
            continue
        where = _enclosing(parents)
        if alias in NUMPY_ALIASES:
            if fname == "arange" and not _has_dtype(node, fname):
                findings.append(Finding(
                    rule=RULE_ARANGE, file=sf.rel, line=node.lineno,
                    message=(f"np.arange without explicit dtype in "
                             f"`{where}` (`{_snippet(node)}`)"),
                    hint="numpy's default integer is the platform C long "
                         "(int32 on Windows); pin dtype=np.int64 (or the "
                         "width the consumer needs)"))
            elif fname in ("array", "asarray") \
                    and not _has_dtype(node, fname) and node.args \
                    and isinstance(node.args[0], _LITERAL_NODES):
                findings.append(Finding(
                    rule=RULE_LITERAL, file=sf.rel, line=node.lineno,
                    message=(f"np.{fname} on a literal without explicit "
                             f"dtype in `{where}` (`{_snippet(node)}`)"),
                    hint="element-derived dtype is platform-dependent for "
                         "int content; pin dtype= at the call"))
        elif police_jnp and alias in JNP_ALIASES:
            if fname in ("zeros", "ones", "empty", "full", "arange",
                         "linspace") and not _has_dtype(node, fname):
                findings.append(Finding(
                    rule=RULE_JNP, file=sf.rel, line=node.lineno,
                    message=(f"jnp.{fname} without explicit dtype in "
                             f"`{where}` (`{_snippet(node)}`)"),
                    hint="jax's default dtype flips with the x64 mode "
                         "(jax.enable_x64); pin dtype= at the call"))
    return findings


def _snippet(node: ast.AST) -> str:
    try:
        text = ast.unparse(node)
    except Exception:
        return "<call>"
    return text if len(text) <= 60 else text[:57] + "..."


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    for rel in HOT_MODULES:
        sf = ctx.source(rel)
        if sf is not None:
            findings.extend(_check_module(sf, police_jnp=rel in JNP_MODULES))
    return findings
