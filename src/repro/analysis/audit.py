"""flcheck orchestration: build program subjects from a live experiment
and run the rule catalogue + AST lint over them.

``collect_subjects`` traces (and, by default, compiles) the server's
block programs exactly as the server would dispatch them — the block of
one that ``run_round`` dispatches, the R-round block of
``run_pipelined``, and the jitted eval fn — so the audited jaxprs/HLO
are the real artifacts, not re-derivations.
``audit_experiment`` is the one entry point: the CLI
(``repro.analysis.cli``), the opt-in build hook
(``build_experiment(..., audit=...)``), and the end-to-end test all call
it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.pylint_jax import lint_paths
from repro.analysis.report import AuditError, Finding, Report
from repro.analysis.rules import run_rules
from repro.core.engine import _donate_argnums


@dataclasses.dataclass
class ProgramSubject:
    """One server-built program under audit."""
    name: str
    jaxpr: Any = None             # ClosedJaxpr from jax.make_jaxpr
    hlo: Optional[str] = None     # compiled.as_text(), when compiled
    expect_donation: tuple = ()   # argnums the build asked to donate


@dataclasses.dataclass
class AuditContext:
    """Everything the rules see: the subjects plus build metadata."""
    subjects: List[ProgramSubject]
    server: Any = None
    task: str = ""
    strategy: str = ""
    backend: str = ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _subject(name: str, fn, args, *, compile: bool, expect_donation=(),
             findings: Optional[List[Finding]] = None) -> ProgramSubject:
    s = ProgramSubject(name=name, expect_donation=tuple(expect_donation))
    try:
        s.jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as e:            # surface, don't crash the audit
        if findings is not None:
            findings.append(Finding(
                "audit", "warning", f"could not trace: {e}", subject=name))
    if compile:
        try:
            s.hlo = fn.lower(*args).compile().as_text()
        except Exception as e:
            if findings is not None:
                findings.append(Finding(
                    "audit", "warning", f"could not compile: {e}",
                    subject=name))
    return s


def collect_subjects(server, eval_data=None, eval_every: int = 1,
                     compile: bool = True,
                     findings: Optional[List[Finding]] = None
                     ) -> List[ProgramSubject]:
    """Trace/compile the server's block programs as audit subjects.

    ``round[...]`` is the block of one, without eval, that
    ``run_round`` dispatches; ``block[... xR]`` is the
    ``rounds_per_dispatch``-round block with on-device eval that
    ``run_pipelined`` dispatches when R > 1; ``eval`` is the jitted eval
    fn.  Shapes come from the server's real data; nothing is executed —
    ``lower().compile()`` only.
    """
    subjects: List[ProgramSubject] = []
    eng = server._engine
    # the audit's own make_jaxpr/lower calls fire the engine's on_trace
    # hook; those traces are not dispatch-cache misses, so keep them out
    # of the traced_participant_counts ledger the cache-stability rule
    # reads (the hook holds a reference to the list — mutate in place)
    ledger = eng.traced_participant_counts
    snapshot = list(ledger)
    try:
        _collect(subjects, server, eng, eval_data, eval_every, compile,
                 findings)
    finally:
        ledger[:] = snapshot
    return subjects


def _collect(subjects, server, eng, eval_data, eval_every, compile,
             findings):
    params = server.global_params
    name = server.strategy.name
    rpd = server.rounds_per_dispatch
    programs = [(f"round[{name}]", 1, 0, None)]
    if rpd > 1:
        programs.append((f"block[{name} x{rpd}]", rpd,
                         eval_every if eval_data is not None else 0,
                         eval_data))
    donate = _donate_argnums(True, argnums=(0, 1), backend=eng.backend)
    for label, n_rounds, every, batch in programs:
        args = (params, _sds((2,), jnp.uint32), eng.data, eng.mask, batch,
                _sds((), jnp.int32))
        subjects.append(_subject(
            label, eng.fused_rounds(n_rounds, every), args,
            compile=compile, expect_donation=donate, findings=findings))
    if eval_data is not None:
        subjects.append(_subject(
            "eval", server._eval, (params, eval_data), compile=compile,
            findings=findings))


def audit_experiment(experiment, *, compile: bool = True,
                     lint: bool = True,
                     lint_roots: Optional[Sequence[str]] = None,
                     strict: bool = False) -> Report:
    """Audit a built :class:`repro.core.api.Experiment` (or any object
    with ``.server`` / ``.eval_data``): run every rule over its block
    programs plus the AST lint over the package source.

    ``strict=True`` raises :class:`AuditError` when any error-severity
    finding survives — the contract gate used by
    ``build_experiment(..., audit=True)`` and ``fl_train --audit``.
    """
    server = getattr(experiment, "server", experiment)
    eval_data = getattr(experiment, "eval_data", None)
    cfg = getattr(experiment, "cfg", None)
    report = Report()
    subjects = collect_subjects(server, eval_data=eval_data,
                                compile=compile,
                                findings=report.findings)
    ctx = AuditContext(
        subjects=subjects, server=server,
        task=getattr(cfg, "task", ""),
        strategy=server.strategy.name,
        backend=server._engine.backend)
    report.extend(run_rules(ctx))
    if lint:
        report.extend(lint_paths(lint_roots))
    if strict and not report.ok:
        raise AuditError(report)
    return report
