"""flcheck: a static program auditor for the FL round engine.

Machine-checks the invariants the engine's performance story depends on
(DESIGN.md §8): one device->host sync per fused block, honored buffer
donation, no f64 / weak-type leaks into round programs, no host
callbacks inside fused scans, and compile-cache stability under
permuted participant sets — over (a) the jaxprs of the server's block
programs, (b) the compiled HLO text, and (c) the Python AST of
``src/repro``.

    python -m repro.analysis.cli --task mlp --strategy fedbwo --strict

NOTE: ``repro.core`` imports this package (for ``AuditError``), so only
the dependency-free pieces (walker, report) are imported eagerly; the
audit/rules layers — which import ``repro.core`` — load lazily on first
attribute access.
"""
from repro.analysis.report import (AuditError, Finding, Report,
                                   SEVERITIES)
from repro.analysis.walker import (CALLBACK_PRIMITIVES, EqnSite,
                                   count_primitives, iter_avals,
                                   iter_sites, jaxpr_has_primitive,
                                   walk_jaxpr)

_LAZY = {
    "RULES": "repro.analysis.rules",
    "rule": "repro.analysis.rules",
    "run_rules": "repro.analysis.rules",
    "AuditContext": "repro.analysis.audit",
    "ProgramSubject": "repro.analysis.audit",
    "audit_experiment": "repro.analysis.audit",
    "collect_subjects": "repro.analysis.audit",
    "lint_paths": "repro.analysis.pylint_jax",
    "lint_source": "repro.analysis.pylint_jax",
}

__all__ = ["AuditError", "Finding", "Report", "SEVERITIES",
           "CALLBACK_PRIMITIVES", "EqnSite",
           "count_primitives", "iter_avals", "iter_sites",
           "jaxpr_has_primitive", "walk_jaxpr",
           *_LAZY]


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
