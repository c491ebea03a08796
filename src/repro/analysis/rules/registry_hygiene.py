"""FT-REG: registry hygiene for strategies, transports, and channels.

``SimSpec(strategy="wave-congestion-aware")``,
``transport="roce-nack"``, and schedule validation against the channel
vocabulary all assume the registries are fully populated the moment the
module is imported.  Three ways that assumption rots:

* a ``register_*`` call tucked inside a function runs only if someone
  happens to call it — every other entry point sees a hole in the
  registry (module-level loops/``if`` blocks are fine: they execute at
  import);
* two modules registering the same name — whichever imports last wins,
  silently re-anchoring every consumer (the runtime guards raise today,
  but only on the import order that actually collides);
* a registered name no tier-1 test ever references — the registration
  is dead weight at best and silently broken at worst.

Name extraction is static: literal first arguments, plus a one-hop
resolution through module-level assignments for the
``for _p in (IDEAL, ROCE_NACK, STRACK): register_transport(_p)`` idiom
(the profile name is read out of the ``calibrate_transport("name", ...)``
/ ``TransportProfile(name="...")`` constructor).  A registration whose
name cannot be resolved statically is itself a finding: the other two
checks are blind to it.
"""

from __future__ import annotations

import ast

from ..common import Context, Finding, SourceFile, call_name, iter_parented

RULE_TOPLEVEL = "FT-REG-TOPLEVEL"
RULE_DUP = "FT-REG-DUP"
RULE_UNTESTED = "FT-REG-UNTESTED"
RULE_OPAQUE = "FT-REG-OPAQUE"
RULE_IDS = (RULE_TOPLEVEL, RULE_DUP, RULE_UNTESTED, RULE_OPAQUE)

SRC_DIR = "src"
TESTS_DIR = "tests"

#: register function -> which argument carries the public name.
#: ``register_channel(value, "CH_NAME")`` names via arg 1; the others
#: via arg 0 (a literal string or a resolvable profile object).
REGISTER_FUNCS = {
    "register_strategy": 0,
    "register_transport": 0,
    "register_channel": 1,
}

#: Constructor calls whose name= (or first string arg) defines the
#: registered name when a profile object is passed by variable.
_NAME_BEARING_CTORS = ("TransportProfile", "calibrate_transport")


def _module_assignments(tree: ast.Module) -> dict[str, ast.expr]:
    """Module-level simple assignments: name -> value expression."""
    out: dict[str, ast.expr] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None \
                and isinstance(node.target, ast.Name):
            out[node.target.id] = node.value
    return out


def _name_from_ctor(call: ast.Call) -> str | None:
    if call_name(call).split(".")[-1] not in _NAME_BEARING_CTORS:
        return None
    for kw in call.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def _resolve_name(arg: ast.expr, assigns: dict[str, ast.expr],
                  loop_bindings: dict[str, list[ast.expr]]) -> list[str] | None:
    """Registered name(s) for one register-call argument, or None when
    it cannot be resolved statically."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    candidates: list[ast.expr] = []
    if isinstance(arg, ast.Name):
        if arg.id in loop_bindings:
            candidates = loop_bindings[arg.id]
        elif arg.id in assigns:
            candidates = [assigns[arg.id]]
    out: list[str] = []
    for c in candidates:
        if isinstance(c, ast.Name) and c.id in assigns:
            c = assigns[c.id]
        if isinstance(c, ast.Call):
            name = _name_from_ctor(c)
            if name is None:
                return None
            out.append(name)
        else:
            return None
    return out or None


def _loop_bindings(parents: tuple[ast.AST, ...]) -> dict[str, list[ast.expr]]:
    """Bindings from enclosing module-level ``for x in (a, b, c):``."""
    out: dict[str, list[ast.expr]] = {}
    for p in parents:
        if isinstance(p, ast.For) and isinstance(p.target, ast.Name) \
                and isinstance(p.iter, (ast.Tuple, ast.List)):
            out[p.target.id] = list(p.iter.elts)
    return out


def _scan_module(sf: SourceFile) -> tuple[list[Finding],
                                          list[tuple[str, str, int, str]]]:
    """(findings, registrations) where each registration is
    (register func, resolved name, line, file)."""
    findings: list[Finding] = []
    regs: list[tuple[str, str, int, str]] = []
    assigns = _module_assignments(sf.tree)
    for node, parents in iter_parented(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = call_name(node).split(".")[-1]
        if fname not in REGISTER_FUNCS:
            continue
        in_function = any(isinstance(
            p, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                ast.ClassDef)) for p in parents)
        if in_function:
            findings.append(Finding(
                rule=RULE_TOPLEVEL, file=sf.rel, line=node.lineno,
                message=(f"`{fname}` call inside a function/class body "
                         f"(`{_snippet(node)}`) — the registry is only "
                         f"populated if that code happens to run"),
                hint="move the registration to module top level so it "
                     "executes at import time"))
            continue
        arg_idx = REGISTER_FUNCS[fname]
        if len(node.args) <= arg_idx:
            continue
        replace = any(kw.arg == "replace" for kw in node.keywords)
        names = _resolve_name(node.args[arg_idx], assigns,
                              _loop_bindings(parents))
        if names is None:
            findings.append(Finding(
                rule=RULE_OPAQUE, file=sf.rel, line=node.lineno,
                message=(f"`{fname}` with a statically unresolvable name "
                         f"(`{_snippet(node)}`)"),
                hint="register with a literal name (or a module-level "
                     "constructor with a literal name=) so uniqueness "
                     "and test coverage stay checkable"))
            continue
        if not replace:
            for name in names:
                regs.append((fname, name, node.lineno, sf.rel))
    return findings, regs


def _snippet(node: ast.AST) -> str:
    try:
        text = ast.unparse(node)
    except Exception:
        return "<call>"
    return text if len(text) <= 60 else text[:57] + "..."


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    regs: list[tuple[str, str, int, str]] = []
    for sf in ctx.sources(SRC_DIR):
        f, r = _scan_module(sf)
        findings.extend(f)
        regs.extend(r)

    # repo-wide uniqueness per registry kind
    seen: dict[tuple[str, str], tuple[int, str]] = {}
    for fname, name, line, rel in regs:
        key = (fname, name)
        if key in seen:
            first_line, first_rel = seen[key]
            findings.append(Finding(
                rule=RULE_DUP, file=rel, line=line,
                message=(f"`{fname}({name!r})` registered more than once "
                         f"(first at {first_rel})"),
                hint="pick a unique name, or pass replace=True at the "
                     "site that deliberately overrides"))
        else:
            seen[key] = (line, rel)

    # every registered name must be referenced by at least one test
    test_blobs = [sf.text for sf in ctx.sources(TESTS_DIR)]
    for (fname, name), (line, rel) in sorted(seen.items(),
                                             key=lambda kv: kv[1]):
        if not any(name in blob for blob in test_blobs):
            findings.append(Finding(
                rule=RULE_UNTESTED, file=rel, line=line,
                message=(f"registered name {name!r} ({fname}) is not "
                         f"referenced by any test"),
                hint="add a test that resolves the name through the "
                     "registry (a strategy matrix row or a direct "
                     "resolve_* assertion both count)"))
    return findings
