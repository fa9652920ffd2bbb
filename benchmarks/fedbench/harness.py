"""One run of one cell: set-up, the measured window, the check.

Set-up generates the cell's data from the seed (``gen``), builds the
experiment through ``build_experiment(cfg, client_data=...,
eval_data=...)`` and drives it through its first rounds with the
window's own call, ``run_federated``, keeping the weights after each
call.  That warms every program the window runs.  The window is one
more ``run_federated`` call over a fixed number of rounds (whole blocks
on a fused cell), with ``tau`` out of reach and patience above the round
count so that nothing stops it early; the clock stops after
``block_until_ready``.  Then the peak device memory is read, the
program's state is freed and the plain reference decides ``correct``
(``check``).  Every metric is read by its own module under
``metrics/``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Union

import jax
import numpy as np

from fedbench import check, flops, gen, spec, traces
from fedbench.reference import Protocol, Reference, RunRecord

WINDOW_SPAN = "fedbench.window"
SERVER_SPANS = ("run_round", "evaluate", "dispatch_block", "finish_block")
NEVER = 2.0                     # a tau no accuracy reaches


class NoChip(RuntimeError):
    pass


def say(*parts):
    print("fedbench:", *parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------ set-up --
class CompileLog:
    """Backend compile seconds from JAX's monitoring events (a
    persistent-cache hit reports its retrieval time), stamped with the
    host clock at which each ended."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.entries: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.entries.append((time.perf_counter(),
                                 kwargs.get("fun_name", "?"), duration))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.entries if t0 <= e[0] <= t1]


def device_check(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX sees {len(devices)} "
                     f"{devices[0].platform} device(s)); nothing was run")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, {len(devices)} found")
    return devices


def protocol(cell: dict) -> Protocol:
    fl, p = cell["fl"], cell["protocol"]
    return Protocol(strategy=fl["strategy"],
                    local_epochs=fl["local_epochs"], lr=fl["lr"],
                    mh_pop=fl.get("mh_pop", 1),
                    mh_generations=fl.get("mh_generations", 0),
                    fitness_batches=p["fitness_batches"],
                    client_ratio=fl.get("client_ratio", 1.0),
                    bwo=p.get("bwo", {}),
                    genome=p.get("genome", "flat"),
                    genome_scale=p.get("genome_scale", 0.05))


def fl_config(cell: dict, cfg: dict, server_seed: int):
    """The program's ``FLConfig`` for the cell.  A key that ``FLConfig``
    no longer has (an engine knob that became derived) is skipped with
    a note."""
    from repro.core.api import FLConfig
    fields = {f.name for f in dataclasses.fields(FLConfig)}
    kw: Dict[str, Any] = {"task": cfg["task"],
                          "n_clients": cell["traffic"]["n_clients"],
                          "batch_size": cell["traffic"]["batch_size"],
                          "server_seed": server_seed, "tau": NEVER}
    for key, value in cell["fl"].items():
        if key in fields:
            kw[key] = value
        else:
            say(f"note: FLConfig has no {key!r}; skipped")
    return FLConfig(**kw)


def host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


def round_log(rl) -> dict:
    info = rl.info
    out = {"scores": np.asarray(info["scores"], np.float64),
           "eval_loss": float(rl.test_loss), "eval_acc": float(rl.test_acc)}
    if "best_client" in info:
        out["best"] = int(info["best_client"])
    if "participants" in info:
        out["participants"] = [int(k) for k in info["participants"]]
    return out


def rounds_per_call(server) -> int:
    """Rounds one ``run_federated`` call dispatches at once: a fused
    block on the batched engine, else one."""
    rpd = int(getattr(server, "rounds_per_dispatch", 1))
    fused = rpd > 1 and getattr(server, "engine", "") == "batched"
    return rpd if fused else 1


def window_rounds(seconds: float, hint_s: float, per_call: int) -> int:
    calls = max(1, round(seconds / (hint_s * per_call)))
    return calls * per_call


def annotate(server):
    """TraceAnnotation spans on the server instance's public methods."""
    for name in SERVER_SPANS:
        fn = getattr(server, name, None)
        if fn is None:
            say(f"note: Server has no {name!r}; no span for it")
            continue

        def wrapped(*a, _fn=fn, _label=f"Server.{name}", **k):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **k)
        setattr(server, name, wrapped)


# --------------------------------------------------------------- run --
@dataclasses.dataclass
class Prepared:
    """A built experiment driven through its first rounds."""
    cell: dict
    cfg: dict
    proto: Protocol
    traffic: Union[gen.Traffic, gen.TokenTraffic]
    data: gen.Dataset
    flcfg: Any
    exp: Any
    eval_data: Any
    first: RunRecord             # initial weights, kept states, logs
    per_call: int                # rounds per run_federated call


@dataclasses.dataclass
class Outcome:
    result: dict                 # the JSON line
    numbers: Dict[str, float]    # every number the check computed
    checks: Dict[str, dict]


def prepare(cell: dict, seed: int) -> Prepared:
    """Generates the cell's data from ``seed``, builds the experiment and
    drives it through its first rounds with the window's own call,
    keeping the weights after each call."""
    from repro.core.api import build_experiment
    from repro.core.protocol import StopConditions, run_federated

    cfg = spec.config(cell["config"])
    traffic = gen.traffic(cell["traffic"])
    data = gen.generate(traffic, seed)
    flcfg = fl_config(cell, cfg, data.server_seed)
    eval_data = jax.device_put(data.test)
    exp = build_experiment(flcfg, client_data=[jax.device_put(c)
                                               for c in data.clients],
                           eval_data=eval_data)
    server = exp.server
    say(f"engine={server.engine} rounds_per_dispatch="
        f"{getattr(server, 'rounds_per_dispatch', '?')} pipeline_blocks="
        f"{getattr(server, 'pipeline_blocks', '?')}")
    first = RunRecord(w0=host(server.global_params), snapshots={}, logs=[])
    per_call = rounds_per_call(server)
    for _ in range(math.ceil(int(cell["window"]["check_rounds"]) / per_call)):
        out = run_federated(server, eval_data, StopConditions(
            max_rounds=per_call, patience=per_call + 1, tau=NEVER),
            eval_every=flcfg.eval_every)
        first.logs += [round_log(rl) for rl in out]
        first.snapshots[len(first.logs)] = host(server.global_params)
    return Prepared(cell=cell, cfg=cfg, proto=protocol(cell),
                    traffic=traffic, data=data, flcfg=flcfg, exp=exp,
                    eval_data=eval_data, first=first, per_call=per_call)


def exact_counts(server, traffic, proto: Protocol,
                 logs: List[dict]) -> Dict[str, float]:
    meter = server.meter
    n_part = max(int(proto.client_ratio * traffic.n_clients), 1)
    out = {"bytes_rounds_off": float(check.bytes_rounds_off(
        meter.uplink, meter.downlink, proto.is_fedx, traffic.n_clients,
        n_part, meter.model_bytes, len(logs)))}
    if proto.is_fedx:
        out["winner_not_argmin"] = float(check.winner_not_argmin(logs))
    return out


def reference(p: Prepared, **kw) -> Reference:
    return Reference(spec.model(p.cell["config"]), p.cfg, p.proto,
                     p.data.clients, p.data.test, p.data.server_seed, **kw)


def follow(ref: Reference, first: RunRecord) -> RunRecord:
    """The reference's own run over the rounds ``first`` kept."""
    return ref.run(max(first.snapshots), keep_after=sorted(first.snapshots))


def judge(run: RunRecord, ref: Reference, ref_run: RunRecord,
          is_fedx: bool) -> Dict[str, float]:
    out = check.consistency(run, ref, is_fedx)
    out.update(check.trajectory(run, ref_run, is_fedx))
    out.update(check.first_round(run, ref, ref_run, is_fedx))
    return out


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> Outcome:
    bench = spec.benchmark()
    cell = spec.workload(cell_name, bench)
    devices = (device_check(int(cell.get("chips", 1))) if require_tpu
               else jax.devices())
    dev = devices[0]
    try:
        peak = spec.peaks(dev.device_kind)
    except KeyError:
        if require_tpu:
            raise
        peak = None
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        say(f"compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    from repro.core.protocol import StopConditions, run_federated

    p = prepare(cell, seed)
    server = p.exp.server
    setup_blocks = len(server.meter.block_timings)
    say(f"set-up peak_bytes_in_use "
        f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")

    # the window
    n_window = window_rounds(seconds, float(cell["window"]["round_s_hint"]),
                             p.per_call)
    stop = StopConditions(max_rounds=n_window, patience=n_window + 1,
                          tau=NEVER)
    trace_dir = None
    if trace:
        annotate(server)
        trace_dir = tempfile.mkdtemp(prefix="fedbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        window_logs = run_federated(server, p.eval_data, stop,
                                    eval_every=p.flcfg.eval_every)
        jax.block_until_ready(server.global_params)
    t_w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.between(t_w0, t_w1)
    say(f"compilations inside the window: {len(in_window)} "
        f"{[e[1] for e in in_window]}")
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    wlogs = [round_log(rl) for rl in window_logs]
    numbers = exact_counts(server, p.traffic, p.proto, p.first.logs + wlogs)
    window_blocks = server.meter.block_timings[setup_blocks:]
    failed = sum(not (np.all(np.isfinite(l["scores"]))
                      and (math.isnan(l["eval_loss"])
                           or math.isfinite(l["eval_loss"])))
                 for l in wlogs)
    ctx = {
        "setup_s": t_w0 - t_start, "window_s": t_w1 - t_w0,
        "window_rounds": n_window, "peak_bytes": peak_bytes, "peak": peak,
        "model_flops": flops.rounds_flops(
            2.0 * spec.model(cell["config"]).forward_macs(p.cfg), p.proto,
            p.data.n_batches, p.traffic.batch_size, wlogs, p.traffic.n_test),
        "window_blocks": window_blocks, "trace": None,
        "setup_compile_s": sum(e[2] for e in compiles.between(t_start, t_w0)),
    }
    # free the program's state before the reference runs
    p.exp = p.eval_data = server = window_logs = None
    gc.collect()

    if trace_dir is not None:
        devs, spans = traces.load(traces.find_xplane(trace_dir),
                                  [WINDOW_SPAN] + [f"Server.{n}" for n in
                                                   SERVER_SPANS])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = traces.reduce(devs, spans,
                                     traces.window_of(spans, WINDOW_SPAN))

    # the check
    t_r0 = time.perf_counter()
    ref = reference(p)
    numbers.update(judge(p.first, ref, follow(ref, p.first), p.proto.is_fedx))
    say(f"reference and check {time.perf_counter() - t_r0:.1f} s after a "
        f"{ctx['window_s']:.1f} s window")
    checks = check.compare(numbers, cell.get("limits"))
    correct = check.passed(checks) and failed == 0

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bench, cell["name"], kind):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": n_window, "failed": int(failed),
                              "metrics": metrics, "device": device}
    reduced = ctx["trace"]
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return Outcome(result=result, numbers=numbers, checks=checks)
