"""flcheck tests: every rule fires on its known-bad fixture and stays
quiet on the known-good one, plus an end-to-end audit of a real
fused+pipelined mlp build (zero error-severity findings on main)."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (AuditError, Finding, Report,
                            count_primitives, iter_avals, iter_sites,
                            jaxpr_has_primitive)
from repro.analysis.audit import (AuditContext, ProgramSubject,
                                  audit_experiment, collect_subjects)
from repro.analysis.pylint_jax import lint_source
from repro.analysis.rules import (RULES, check_cache_stability,
                                  check_donation, run_rules)
from repro.core.api import FLConfig, build_experiment
from repro.core.knobs import parse_audit
from repro.launch.hlo_analysis import (count_host_transfers,
                                       parse_input_output_aliases)


def _errors(findings, rule=None):
    return [f for f in findings if f.severity == "error"
            and (rule is None or f.rule == rule)]


def _subject(fn, *args, name="prog", compile=True, **kw):
    jit = fn if hasattr(fn, "lower") else jax.jit(fn)
    return ProgramSubject(
        name=name, jaxpr=jax.make_jaxpr(fn)(*args),
        hlo=jit.lower(*args).compile().as_text() if compile else None,
        **kw)


def _ctx(*subjects, backend="cpu"):
    return AuditContext(subjects=list(subjects), backend=backend,
                        strategy="fedbwo", task="mlp")


def _with_callback(x):
    jax.debug.callback(lambda v: None, x)
    return x * 2


def _scan_with_callback(xs):
    def body(c, x):
        jax.debug.callback(lambda v: None, c)
        return c + x, x
    return jax.lax.scan(body, jnp.float32(0), xs)


# ------------------------------------------------------------------ walker

def test_walker_scan_multiplier_and_paths():
    jaxpr = jax.make_jaxpr(_scan_with_callback)(jnp.zeros(5, jnp.float32))
    sites = [s for s in iter_sites(jaxpr)
             if s.primitive == "debug_callback"]
    assert sites and sites[0].multiplier == 5
    assert sites[0].in_loop and "scan" in sites[0].path
    counts = count_primitives(jaxpr, ("debug_callback",), weighted=True)
    assert counts == {"debug_callback": 5}


def test_walker_has_primitive_and_avals():
    jaxpr = jax.make_jaxpr(lambda x: jnp.sin(x) + 1)(
        jnp.zeros((3,), jnp.float32))
    assert jaxpr_has_primitive(jaxpr, ("sin",))
    assert not jaxpr_has_primitive(jaxpr, ("conv_general_dilated",))
    assert any(str(a.dtype) == "float32" for a in iter_avals(jaxpr))


# ---------------------------------------------------------- findings model

def test_report_model():
    r = Report([Finding("r1", "error", "boom"),
                Finding("r2", "warning", "meh"),
                Finding("r3", "info", "fyi")])
    assert not r.ok and len(r.errors) == 1 and len(r.warnings) == 1
    assert r.counts() == {"info": 1, "warning": 1, "error": 1}
    text = r.render()
    assert "boom" in text and "fyi" not in text
    assert "fyi" in r.render(show_info=True)
    with pytest.raises(ValueError):
        Finding("r", "fatal", "bad severity")
    err = AuditError(r)
    assert "r1: boom" in str(err) and err.report is r


def test_parse_audit_knob():
    assert parse_audit(None) == "off"
    assert parse_audit(False) == "off"
    assert parse_audit(True) == "strict"
    assert parse_audit("REPORT") == "report"
    with pytest.raises(ValueError):
        parse_audit("loud")


# ------------------------------------------------------- one-sync-per-block

def test_one_sync_good_program_is_clean():
    s = _subject(lambda x: x * 2 + 1, jnp.zeros((4,), jnp.float32))
    findings = run_rules(_ctx(s), only=("one-sync-per-block",))
    assert not _errors(findings)


def test_one_sync_flags_callback_in_jaxpr_and_hlo():
    s = _subject(_with_callback, jnp.zeros((4,), jnp.float32))
    errs = _errors(run_rules(_ctx(s), only=("one-sync-per-block",)))
    assert errs, "callback program must fail one-sync-per-block"
    # both the jaxpr walk and the HLO count see the host edge
    assert any("debug_callback" in f.message for f in errs)
    assert any("host-transfer" in f.message for f in errs)


def test_count_host_transfers_loop_corrected():
    hlo = textwrap.dedent("""\
        HloModule jit_loop

        %body (p: (s32[], f32[8], token[])) -> (s32[], f32[8], token[]) {
          %p = (s32[], f32[8], token[]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %v = f32[8] get-tuple-element(%p), index=1
          %tk = token[] get-tuple-element(%p), index=2
          %of = token[] outfeed(%v, %tk), outfeed_config="x"
          ROOT %t = (s32[], f32[8], token[]) tuple(%i, %v, %of)
        }

        %cond (q: (s32[], f32[8], token[])) -> pred[] {
          %q = (s32[], f32[8], token[]) parameter(0)
          %j = s32[] get-tuple-element(%q), index=0
          %c = s32[] constant(5)
          ROOT %lt = pred[] compare(%j, %c), direction=LT
        }

        ENTRY %main (a: f32[8]) -> f32[8] {
          %a = f32[8] parameter(0)
          %tok = token[] after-all()
          %init = (s32[], f32[8], token[]) tuple()
          %wl = (s32[], f32[8], token[]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
          ROOT %out = f32[8] get-tuple-element(%wl), index=1
        }
    """)
    assert count_host_transfers(hlo) == {"outfeed": 5.0}
    assert count_host_transfers(hlo, loop_corrected=False) == \
        {"outfeed": 1.0}


# --------------------------------------------------------- donation-honored

def test_donation_dropped_is_error():
    hlo_no_alias = "HloModule jit_f\nENTRY %main () -> f32[2] {}"
    errs = _errors(check_donation(hlo_no_alias, expect_donation=True))
    assert errs and "dropped" in errs[0].message


def test_donation_honored_on_real_compile():
    x = jnp.zeros((8,), jnp.float32)
    hlo = jax.jit(lambda x: x + 1,
                  donate_argnums=0).lower(x).compile().as_text()
    aliases = parse_input_output_aliases(hlo)
    assert aliases == [((), 0, ())]
    findings = check_donation(hlo, expect_donation=True)
    assert not _errors(findings)
    assert any("honored" in f.message for f in findings)
    # aliasing nobody asked for is surfaced as a warning
    assert any(f.severity == "warning"
               for f in check_donation(hlo, expect_donation=False))


def test_parse_input_output_aliases_header():
    hlo = ("HloModule jit_f, input_output_alias={ {0}: (0, {}, "
           "may-alias), {1}: (2, {0}, must-alias) }, "
           "entry_computation_layout={(f32[2])->f32[2]}")
    assert parse_input_output_aliases(hlo) == [((0,), 0, ()),
                                               ((1,), 2, (0,))]


# ------------------------------------------------------------------- no-f64

def test_no_f64_flags_x64_program():
    with jax.enable_x64():
        jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.0))
    s = ProgramSubject(name="x64", jaxpr=jaxpr)
    errs = _errors(run_rules(_ctx(s), only=("no-f64",)), "no-f64")
    assert errs and "float64" in errs[0].message


def test_no_f64_clean_on_f32():
    s = _subject(lambda x: x * 2, jnp.zeros((4,), jnp.float32),
                 compile=False)
    assert not _errors(run_rules(_ctx(s), only=("no-f64",)))


# ------------------------------------------------- no-weak-type-promotion

def test_weak_type_output_warns():
    jaxpr = jax.make_jaxpr(lambda x: x * 2)(1.0)   # python-float provenance
    s = ProgramSubject(name="weak", jaxpr=jaxpr)
    findings = run_rules(_ctx(s), only=("no-weak-type-promotion",))
    assert any(f.severity == "warning" for f in findings)


def test_strong_type_output_is_clean():
    s = _subject(lambda x: x * 2, jnp.zeros((4,), jnp.float32),
                 compile=False)
    findings = run_rules(_ctx(s), only=("no-weak-type-promotion",))
    assert not any(f.severity == "warning" for f in findings)


# ------------------------------------------------- no-host-callback-in-scan

def test_callback_inside_scan_is_error_with_multiplier():
    s = _subject(_scan_with_callback, jnp.zeros(5, jnp.float32),
                 compile=False)
    errs = _errors(run_rules(_ctx(s), only=("no-host-callback-in-scan",)))
    assert errs and "x5" in errs[0].message


def test_callback_outside_loop_passes_scan_rule():
    s = _subject(_with_callback, jnp.zeros((4,), jnp.float32),
                 compile=False)
    assert not _errors(run_rules(_ctx(s),
                                 only=("no-host-callback-in-scan",)))


# -------------------------------------------------- compile-cache-stability

def test_cache_stability_known_bad():
    sig_a, sig_b = (("(4, 8)", "float32"),), (("(3, 8)", "float32"),)
    errs = _errors(check_cache_stability([sig_a, sig_b]))
    assert errs and "distinct signatures" in errs[0].message
    errs = _errors(check_cache_stability([sig_a, sig_a],
                                         traced_counts=[4, 4]))
    assert errs and "traced more than once" in errs[0].message


def test_cache_stability_known_good():
    sig = (("(4, 8)", "float32"),)
    findings = check_cache_stability([sig, sig, sig], traced_counts=[4])
    assert not _errors(findings)
    assert any(f.severity == "info" for f in findings)


# ----------------------------------------------------------------- AST lint

def test_lint_host_conversion_in_jit():
    src = textwrap.dedent("""\
        import jax

        @jax.jit
        def step(x):
            return float(x) + 1
    """)
    findings = lint_source(src, "mod.py")
    assert _errors(findings, "host-conversion-in-jit")


def test_lint_shape_conversions_and_allowlist_pass():
    src = textwrap.dedent("""\
        import jax

        @jax.jit
        def step(pop, frac):
            P, D = pop.shape
            keep = int(P * frac)
            n = int(len(pop.shape))
            bad = float(pop)  # flcheck: ok
            return keep + n
    """)
    assert not lint_source(src, "mod.py")


def test_lint_traced_by_combinator_not_decorator():
    src = textwrap.dedent("""\
        import jax

        def body(c, x):
            return c + int(x), x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """)
    assert _errors(lint_source(src, "mod.py"), "host-conversion-in-jit")


def test_lint_paired_host_conversions():
    bad = textwrap.dedent("""\
        def fetch(a, b):
            return float(a), float(b)
    """)
    findings = lint_source(bad, "mod.py")
    assert any(f.rule == "paired-host-conversions" for f in findings)
    good = textwrap.dedent("""\
        import jax

        def fetch(a, b):
            a, b = jax.device_get((a, b))
            return float(a), float(b)
    """)
    assert not lint_source(good, "mod.py")


def test_lint_mutable_default_arg():
    src = textwrap.dedent("""\
        import jax.numpy as jnp

        def f(x, init=jnp.zeros((3,)), acc=[]):
            return x
    """)
    findings = lint_source(src, "mod.py")
    assert sum(f.rule == "mutable-default-arg" for f in findings) == 2


# -------------------------------------------------------------- end to end

def _small_cfg(**kw):
    base = dict(task="mlp", strategy="fedbwo", n_clients=4, n_train=240,
                n_test=60, batch_size=8, local_epochs=1, mh_pop=2,
                mh_generations=1, max_rounds=3)
    base.update(kw)
    return FLConfig(**base)


def test_e2e_fused_pipelined_mlp_build_audits_clean():
    exp = build_experiment(_small_cfg(rounds_per_dispatch=3))
    report = audit_experiment(exp)
    assert report.ok, report.render()
    names = {f.subject for f in report.findings}
    assert any(n.startswith("round[") for n in names)
    assert any(n.startswith("block[") and "x3" in n for n in names)
    assert "eval" in names
    # every rule in the catalogue reported something (info at minimum)
    assert set(RULES) <= {f.rule for f in report.findings}


def test_audit_does_not_pollute_trace_ledger():
    exp = build_experiment(_small_cfg(strategy="fedavg"))
    eng = exp.server._engine
    before = list(eng.traced_participant_counts)
    report = audit_experiment(exp, compile=False, lint=False)
    assert report.ok, report.render()
    assert eng.traced_participant_counts == before


def test_audit_strict_raises_on_error(monkeypatch):
    exp = build_experiment(_small_cfg())
    import repro.analysis.rules as rules_mod

    def bomb(ctx):
        return [Finding("planted", "error", "boom")]
    monkeypatch.setitem(rules_mod.RULES, "planted", bomb)
    with pytest.raises(AuditError, match="planted: boom"):
        audit_experiment(exp, compile=False, lint=False, strict=True)


def test_collect_subjects_follow_the_server():
    """A server with single-round dispatches builds the block of one
    only: it is the one round program audited, besides eval."""
    exp = build_experiment(_small_cfg())
    subjects = collect_subjects(exp.server, eval_data=exp.eval_data,
                                compile=False)
    assert {s.name for s in subjects} == {"round[fedbwo]", "eval"}
    assert all(s.jaxpr is not None for s in subjects)


def test_cli_strict_exits_zero_on_main():
    from repro.analysis.cli import main
    assert main(["--task", "mlp", "--strategy", "fedavg", "--strict",
                 "--no-compile"]) == 0
