"""Rule registry: one module per rule family, each exposing
``run(ctx) -> list[Finding]`` plus the ``FT-*`` rule ids it can emit."""

from . import bench_coverage, dtype_drift, jit_retrace, registry_hygiene

#: (family name, module) in report order.  Every module contributes its
#: rule ids via a module-level ``RULE_IDS`` tuple.
FAMILIES = (
    ("jit-retrace", jit_retrace),
    ("dtype-drift", dtype_drift),
    ("registry-hygiene", registry_hygiene),
    ("bench-coverage", bench_coverage),
)

ALL_RULE_IDS = tuple(
    rid for _, mod in FAMILIES for rid in mod.RULE_IDS)
