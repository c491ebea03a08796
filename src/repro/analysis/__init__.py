"""flowcheck: engine-invariant static analysis for the FlowTracer repo.

The correctness story of this codebase rests on invariants that no
general-purpose linter knows about: the numpy and jax engines must stay
bit-identical, jitted code must not retrace or host-sync, registries
must be populated at import time, and benchmark rows must stay in
lockstep with the committed smoke baseline.  ``flowcheck``
encodes each of those contracts as an AST-level rule family and fails CI
on *new* violations (a committed ``flowcheck_baseline.json`` suppresses
— with justification — the pre-existing ones).

    PYTHONPATH=src python -m repro.analysis.flowcheck

The package is deliberately stdlib-only (``ast`` + ``json``): the CI job
needs no numpy/jax install to run it.
"""

from .common import Context, Finding

__all__ = ["Context", "Finding", "collect_findings", "main"]


def __getattr__(name):
    # lazy: importing .flowcheck eagerly would double-import it under
    # `python -m repro.analysis.flowcheck` (runpy warns)
    if name in ("collect_findings", "main"):
        from . import flowcheck
        return getattr(flowcheck, name)
    raise AttributeError(name)
