"""flcheck rule registry: the round engine's machine-checked invariants.

Each rule is a function ``check(ctx) -> Iterable[Finding]`` registered
with the :func:`rule` decorator; :func:`run_rules` runs the whole
catalogue over an ``AuditContext`` (``repro.analysis.audit``) holding
the program subjects (jaxpr + compiled HLO per block program the
server builds) and the live server/engine.  Every rule degrades to an
``info`` finding when its subject is absent (e.g. no compiled HLO in a
``--no-compile`` run) — silence never means "checked and clean".

The catalogue (DESIGN.md §8):

====================== ======== ==========================================
rule                   severity invariant
====================== ======== ==========================================
one-sync-per-block     error    no in-program device->host edge: the
                                block's output fetch is the ONLY sync
donation-honored       error    requested buffer donation survives to
                                ``input_output_alias`` in the HLO
no-f64                 error    no f64/c128 value in any round program
no-weak-type-promotion warning  no weakly-typed program output
no-host-callback-in-   error    no pure/io/debug callback inside a
scan                            fused scan body (it would fire xR)
compile-cache-         error    one executable per participant count;
stability                       avals independent of WHICH participants
====================== ======== ==========================================

Pure helpers (``check_donation``, ``check_cache_stability``) carry the rule logic so tests can drive each
rule's known-bad branch without building a bad engine.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Sequence

import numpy as np

from repro.analysis.report import Finding
from repro.analysis.walker import (CALLBACK_PRIMITIVES, iter_avals,
                                   iter_sites)
from repro.launch.hlo_analysis import (count_host_transfers,
                                       parse_input_output_aliases)

RULES: Dict[str, Callable] = {}


def rule(name: str):
    """Register a check under ``name`` (registration order = run order)."""
    def register(fn):
        fn.rule_name = name
        RULES[name] = fn
        return fn
    return register


def run_rules(ctx, only: Sequence[str] = ()) -> List[Finding]:
    """Run the catalogue (or the ``only`` subset) over ``ctx``."""
    findings: List[Finding] = []
    for name, check in RULES.items():
        if only and name not in only:
            continue
        findings.extend(check(ctx))
    return findings


# ------------------------------------------------------- one-sync-per-block
@rule("one-sync-per-block")
def check_one_sync_per_block(ctx) -> Iterable[Finding]:
    """The fused block's log sync (the caller fetching the program's
    outputs) must be the only device->host edge: the compiled program
    itself may contain no outfeed/send/recv/host-callback ops, and the
    jaxpr no callback primitives anywhere."""
    out: List[Finding] = []
    for s in ctx.subjects:
        if s.jaxpr is not None:
            for site in iter_sites(s.jaxpr):
                if site.primitive in CALLBACK_PRIMITIVES:
                    out.append(Finding(
                        "one-sync-per-block", "error",
                        f"host callback primitive "
                        f"{site.primitive!r} in the program — a "
                        f"device->host edge besides the output fetch",
                        subject=s.name,
                        location="/".join(site.path) or "<top>"))
        if s.hlo is None:
            out.append(Finding(
                "one-sync-per-block", "info",
                "no compiled HLO for this subject; only the jaxpr "
                "side of the rule ran", subject=s.name))
            continue
        xfers = count_host_transfers(s.hlo)
        if xfers:
            detail = ", ".join(f"{k} x{v:g}" for k, v in
                               sorted(xfers.items()))
            out.append(Finding(
                "one-sync-per-block", "error",
                f"in-program host-transfer ops ({detail}) — the block "
                f"must sync with the host exactly once, via its "
                f"output fetch", subject=s.name,
                details={"host_transfers": xfers}))
        else:
            out.append(Finding(
                "one-sync-per-block", "info",
                "0 in-program host-transfer ops", subject=s.name))
    return out


# --------------------------------------------------------- donation-honored
def check_donation(hlo: str, expect_donation: bool,
                   subject: str = "") -> List[Finding]:
    """Pure rule core: compare requested donation against the compiled
    ``input_output_alias`` header."""
    aliases = parse_input_output_aliases(hlo)
    if expect_donation and not aliases:
        return [Finding(
            "donation-honored", "error",
            "buffer donation was requested at build time but the "
            "compiled program aliases no input to any output — the "
            "donation was silently dropped (peak memory doubles)",
            subject=subject)]
    if not expect_donation and aliases:
        return [Finding(
            "donation-honored", "warning",
            f"program aliases {len(aliases)} buffer(s) although the "
            f"build requested no donation", subject=subject,
            details={"aliases": [list(map(list, a[:1])) + [a[1]]
                                 for a in aliases]})]
    msg = (f"donation honored: {len(aliases)} aliased buffer(s)"
           if expect_donation else
           "no donation requested on this backend (CPU aliasing is a "
           "no-op), none expected in the HLO")
    return [Finding("donation-honored", "info", msg, subject=subject)]


@rule("donation-honored")
def check_donation_honored(ctx) -> Iterable[Finding]:
    out: List[Finding] = []
    for s in ctx.subjects:
        if s.hlo is None:
            continue
        out.extend(check_donation(s.hlo, bool(s.expect_donation),
                                  subject=s.name))
    return out


# ------------------------------------------------------------------- no-f64
_F64_TOKEN = re.compile(r"\b(f64|c128)\[")


@rule("no-f64")
def check_no_f64(ctx) -> Iterable[Finding]:
    """FL round programs are fp32 end to end (scores are 4-byte fp32 by
    protocol); any f64 value silently doubles compute, memory, and the
    uplink accounting."""
    out: List[Finding] = []
    for s in ctx.subjects:
        if s.jaxpr is not None:
            bad = sorted({str(a.dtype) for a in iter_avals(s.jaxpr)
                          if str(a.dtype) in ("float64", "complex128")})
            if bad:
                out.append(Finding(
                    "no-f64", "error",
                    f"{'/'.join(bad)} values in the traced program — "
                    f"a stray promotion (x64 mode or a python float "
                    f"literal under enable_x64) doubles every byte",
                    subject=s.name))
        if s.hlo is not None and _F64_TOKEN.search(s.hlo):
            out.append(Finding(
                "no-f64", "error",
                "f64/c128 buffers in the compiled HLO", subject=s.name))
    if not out:
        out.append(Finding("no-f64", "info",
                           f"{len(ctx.subjects)} program(s) clean"))
    return out


# --------------------------------------------------- no-weak-type-promotion
@rule("no-weak-type-promotion")
def check_no_weak_type(ctx) -> Iterable[Finding]:
    """Weakly-typed program outputs (python-scalar provenance) take
    their dtype from whatever they later touch — a downstream consumer
    can silently promote an entire carry."""
    out: List[Finding] = []
    for s in ctx.subjects:
        if s.jaxpr is None:
            continue
        jaxpr = getattr(s.jaxpr, "jaxpr", s.jaxpr)
        weak = [str(v.aval) for v in jaxpr.outvars
                if getattr(v.aval, "weak_type", False)]
        if weak:
            out.append(Finding(
                "no-weak-type-promotion", "warning",
                f"{len(weak)} weakly-typed program output(s) "
                f"({', '.join(weak[:4])}) — pin dtypes with "
                f"jnp.asarray(x, jnp.float32) at the boundary",
                subject=s.name))
    if not out:
        out.append(Finding("no-weak-type-promotion", "info",
                           "no weakly-typed program outputs"))
    return out


# ------------------------------------------------- no-host-callback-in-scan
@rule("no-host-callback-in-scan")
def check_no_callback_in_scan(ctx) -> Iterable[Finding]:
    """A callback inside a fused round scan fires once per iteration —
    R host round-trips smuggled into the 'one sync per block'
    program."""
    out: List[Finding] = []
    for s in ctx.subjects:
        if s.jaxpr is None:
            continue
        for site in iter_sites(s.jaxpr):
            if site.primitive in CALLBACK_PRIMITIVES and site.in_loop:
                out.append(Finding(
                    "no-host-callback-in-scan", "error",
                    f"{site.primitive!r} inside "
                    f"{'/'.join(site.path)} — fires x{site.multiplier} "
                    f"per dispatch, one host round-trip each",
                    subject=s.name, location="/".join(site.path)))
    if not out:
        out.append(Finding("no-host-callback-in-scan", "info",
                           "no callbacks inside loop bodies"))
    return out


# ---------------------------------------------------- compile-cache-stability
def check_cache_stability(aval_sets: Sequence, traced_counts: Sequence[int]
                          = (), subject: str = "") -> List[Finding]:
    """Pure rule core.

    ``aval_sets``: one hashable (shape, dtype) signature per permuted
    participant selection — all must be identical, or each distinct
    participant subset compiles its own executable (the in-program
    participant gather caps the cache at one executable per participant
    count ``m``).  ``traced_counts``: the engine's
    ``traced_participant_counts`` ledger — a repeated entry means one
    ``m`` was traced twice (a cache miss on an already-seen shape).
    """
    out: List[Finding] = []
    sigs = {repr(s) for s in aval_sets}
    if len(sigs) > 1:
        out.append(Finding(
            "compile-cache-stability", "error",
            f"round-program avals depend on WHICH participants are "
            f"sampled ({len(sigs)} distinct signatures across "
            f"permutations) — every round would compile a fresh "
            f"executable instead of one per participant count",
            subject=subject))
    counts = list(traced_counts)
    dupes = sorted({m for m in counts if counts.count(m) > 1})
    if dupes:
        out.append(Finding(
            "compile-cache-stability", "error",
            f"participant count(s) {dupes} traced more than once — the "
            f"per-m compile cache is not being hit", subject=subject))
    if not out:
        out.append(Finding(
            "compile-cache-stability", "info",
            f"stable: {len(aval_sets)} permutation(s), one aval "
            f"signature; traced counts {sorted(set(counts))}",
            subject=subject))
    return out


def _aval_signature(tree) -> tuple:
    import jax
    return tuple(sorted(
        (str(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
        for l in jax.tree.leaves(tree)))


@rule("compile-cache-stability")
def check_cache_stability_rule(ctx) -> Iterable[Finding]:
    """Re-derive the gathered round-program arguments under permuted
    participant subsets and assert their avals (and hence the jit cache
    key) depend only on the participant count ``m``."""
    import jax

    eng = ctx.server._engine
    m = eng.n_participants
    n = eng.n_clients
    rng = np.random.default_rng(0)
    sels = [np.arange(m), np.arange(n)[::-1][:m]] + [
        rng.permutation(n)[:m] for _ in range(2)]
    sigs = []
    for sel in sels:
        sub = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((len(sel),) + a.shape[1:],
                                           a.dtype), eng.data)
        mask = (None if eng.mask is None else
                jax.ShapeDtypeStruct((len(sel),) + eng.mask.shape[1:],
                                     eng.mask.dtype))
        sigs.append(_aval_signature((sub, mask)))
    return check_cache_stability(
        sigs, eng.traced_participant_counts,
        subject=f"round[{ctx.task}/{ctx.strategy}]")
