#!/usr/bin/env python3
"""Device time per FL layer, from a profiler trace of one cell's window.

The program names its device layers with ``jax.named_scope``:
``fl.local_sgd``, ``fl.bwo_fitness``, ``fl.bwo_evolve``,
``fl.server_reduce`` and ``fl.eval``, spelled here literally so that a
renamed scope reads as missing.  A scope lands on the ``op_name`` path
of each HLO operation's metadata, where transforms wrap it
(``jit(block_fn)/vmap(fl.local_sgd)/transpose(jvp())/dot_general``).  A
device op belongs to the innermost layer named on its path.

A TPU trace names each op by its HLO instruction only, inside an "XLA
Modules" event that names its program; the path comes from the
compiled HLO of that program, which the trace keeps on its
``/host:metadata`` plane (read here with a small protobuf reader, so
nothing beyond JAX is imported).  An op without a path (a copy XLA
inserted) takes the layer of the innermost op that encloses it in
time on its device, the ``while`` of a scoped loop spanning its body;
an op with a path that names no layer, or enclosed by nothing, is
``unscoped``.  Layer time is the ops' own time, by ``traces.self_times``'
rule, so the layers sum to the ops' summed own time.

    python3 benchmarks/fedbench/layers.py --workload mlp2nn_fedbwo_noniid \\
        --seed 1234 --seconds 10

runs the cell's set-up, an untraced window, a traced one and an
untraced one again, with no check, and prints one JSON line: device ms
per window round for each layer, the heaviest unscoped ops, the idle
gaps labelled by the program's host spans, the window's real and
computed SGD steps from the server's ledger, and ``round_s`` of each
window.  It compiles with the persistent compile cache off.  Exits
non-zero without a TPU.
"""
from __future__ import annotations

import re
import sys
import time
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SCOPES = ("fl.local_sgd", "fl.bwo_fitness", "fl.bwo_evolve",
          "fl.server_reduce", "fl.eval")
UNSCOPED = "unscoped"
HOST_SPANS = ("Server.run_round", "Server.run_round.sync",
              "Server.evaluate", "Server.evaluate.sync",
              "Server.dispatch_block", "Server.finish_block",
              "Server.finish_block.sync", "Server.finish_block.process")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TOP = 10

Op = Tuple[str, float, float, Optional[str]]  # instr, start_ns, dur_ns, path
_SCOPE = re.compile(r"(?:^|[/(])(fl\.\w+)(?=$|[/)])")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(path: str) -> Optional[str]:
    """The innermost layer named on an ``op_name`` path, looking through
    transform wrappers such as ``vmap(...)`` and ``transpose(jvp(...))``;
    ``None`` when it names none."""
    found = [s for s in _SCOPE.findall(path) if s in SCOPES]
    return found[-1] if found else None


def own_times(devices: Dict[str, Sequence[Op]],
              window: Tuple[float, float]) -> Dict[Tuple[str, str], float]:
    """Own time (ns) of each ``(layer, instruction)`` in ``window``,
    summed over devices.  Ops are clipped to the window; an op's own
    time is its time less that of the ops nested inside it."""
    lo, hi = window
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for ops in devices.values():
        clipped = [(name, max(s, lo), min(s + d, hi), path)
                   for name, s, d, path in ops if min(s + d, hi) > max(s, lo)]
        stack: List[Tuple[float, Tuple[str, str]]] = []   # (end, key)
        for name, a, b, path in sorted(clipped, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][0] <= a:
                stack.pop()
            parent = stack[-1][1] if stack and b <= stack[-1][0] else None
            if path is not None:
                layer = scope_of(path) or UNSCOPED
            else:
                layer = parent[0] if parent is not None else UNSCOPED
            if parent is not None:
                out[parent] -= b - a
            key = (layer, name)
            out[key] += b - a
            stack.append((b, key))
    return dict(out)


def per_layer(own: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    """Own time per layer, every layer listed, ``unscoped`` last."""
    out = {layer: 0.0 for layer in SCOPES + (UNSCOPED,)}
    for (layer, _), t in own.items():
        out[layer] += t
    return out


# ------------------------------------------------------ reading traces --
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _instruction_paths(hlo_proto) -> Dict[str, Optional[str]]:
    """``{instruction: op_name}`` of an ``HloProto`` (hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto.instructions =
    2; HloInstructionProto name = 1, metadata = 7; OpMetadata.op_name =
    2)."""
    out: Dict[str, Optional[str]] = {}
    for f, comp in _fields(_field(hlo_proto, 1, b"")):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name = path = None
            for h, v in _fields(instr):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    op = _field(v, 2)
                    path = bytes(op).decode() if op else None
            out[name] = path
    return out


def hlo_paths(xspace: bytes) -> Dict[str, Dict[str, Optional[str]]]:
    """``{program: {instruction: op_name path or None}}`` from the
    compiled HLO that a trace keeps of each program it ran: the
    ``/host:metadata`` plane's event metadata, named like the "XLA
    Modules" events (``jit_block_fn(<id>)``), each with an ``Hlo
    Proto`` stat.  (XSpace.planes = 1; XPlane name = 2, event_metadata
    = 4, stat_metadata = 5, map entries key = 1, value = 2;
    XEventMetadata name = 2, stats = 5; XStat metadata_id = 1,
    bytes_value = 6; XStatMetadata name = 2.)"""
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for f, plane in _fields(xspace):
        if f != 1 or bytes(_field(plane, 2, b"")) != b"/host:metadata":
            continue
        fields = list(_fields(plane))
        hlo_stat = {_field(entry, 1) for g, entry in fields if g == 5
                    and bytes(_field(_field(entry, 2, b""), 2, b""))
                    == HLO_PROTO_STAT.encode()}
        for g, entry in fields:
            if g != 4:
                continue
            meta = _field(entry, 2, b"")
            name = bytes(_field(meta, 2, b"")).decode()
            for h, stat in _fields(meta):
                if h == 5 and _field(stat, 1) in hlo_stat:
                    out[name] = _instruction_paths(_field(stat, 6, b""))
    return out


def attach(modules: Sequence[Tuple[str, float, float]],
           ops: Sequence[Tuple[str, float, float]],
           paths: Dict[str, Dict[str, Optional[str]]]) -> List[Op]:
    """Each op with the path of its instruction in the program whose
    "XLA Modules" event encloses its start."""
    spans = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for name, s, d in sorted(ops, key=lambda e: e[1]):
        while i + 1 < len(spans) and spans[i + 1][1] <= s:
            i += 1
        path = None
        if spans and spans[i][1] <= s <= spans[i][1] + spans[i][2]:
            path = paths.get(spans[i][0], {}).get(name)
        out.append((name, s, d, path))
    return out


def load(xplane: str) -> Dict[str, List[Op]]:
    """``{device plane: ops with their paths}`` from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    from fedbench import traces
    with open(xplane, "rb") as f:
        raw = f.read()
    paths = hlo_paths(raw)
    devices: Dict[str, List[Op]] = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        mods = [(e.name, e.start_ns, e.duration_ns)
                for e in lines[MODULES_LINE].events] \
            if MODULES_LINE in lines else []
        ops = [(traces.op_name(e.name), e.start_ns, e.duration_ns)
               for e in lines[OPS_LINE].events]
        devices[plane.name] = attach(mods, ops, paths)
    return devices


# ----------------------------------------------------------------- run --
def window(server, eval_data, rounds: int, eval_every: int,
           trace_dir: Optional[str]) -> float:
    """One ``run_federated`` call over ``rounds`` rounds, traced into
    ``trace_dir`` when given; -> seconds per round."""
    import jax
    from fedbench import harness
    from repro.core.protocol import StopConditions, run_federated
    stop = StopConditions(max_rounds=rounds, patience=rounds + 1,
                          tau=harness.NEVER)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        run_federated(server, eval_data, stop, eval_every=eval_every)
        jax.block_until_ready(server.global_params)
    t1 = time.perf_counter()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return (t1 - t0) / rounds


def main(argv: Optional[Iterable[str]] = None) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from fedbench import harness, spec, traces
    cell = spec.workload(args.workload)
    try:
        harness.device_check(int(cell.get("chips", 1)))
    except harness.NoChip as e:
        harness.say(e)
        return 2
    # the persistent cache keys a program with its debug info stripped,
    # so it can hand back an executable built without these scopes
    jax.config.update("jax_enable_compilation_cache", False)
    p = harness.prepare(cell, args.seed)
    server = p.exp.server
    n = harness.window_rounds(args.seconds,
                              float(cell["window"]["round_s_hint"]),
                              p.per_call)
    every = p.flcfg.eval_every
    round_s = [window(server, p.eval_data, n, every, None)]
    steps0 = len(server.meter.sgd_steps)
    trace_dir = tempfile.mkdtemp(prefix="fedbench-trace-")
    round_s.append(window(server, p.eval_data, n, every, trace_dir))
    steps = server.meter.sgd_steps[steps0:]
    round_s.append(window(server, p.eval_data, n, every, None))

    xplane = traces.find_xplane(trace_dir)
    devices = load(xplane)
    _, spans = traces.load(xplane, (harness.WINDOW_SPAN,) + HOST_SPANS)
    win = traces.window_of(spans, harness.WINDOW_SPAN)
    own = own_times(devices, win)
    plain = {k: [(name, s, d) for name, s, d, _ in ops]
             for k, ops in devices.items()}
    reduced = traces.reduce(plain, spans, win)
    lo, hi = win
    self_ns = sum(sum(traces.self_times(
        [(name, max(s, lo), min(s + d, hi)) for name, s, d in ops
         if min(s + d, hi) > max(s, lo)]).values())
        for ops in plain.values())
    unscoped = sorted(((t, name) for (layer, name), t in own.items()
                       if layer == UNSCOPED), reverse=True)[:TOP]
    n_ops = sum(len(ops) for ops in devices.values())
    no_path = Counter(re.sub(r"\.\d+$", "", name)
                      for ops in devices.values()
                      for name, _, _, path in ops if path is None)
    real, computed = (sum(c) for c in zip(*steps)) if steps else (0, 0)
    out = {
        "workload": args.workload, "seed": args.seed, "window_rounds": n,
        "device": jax.devices()[0].device_kind,
        "round_s": {"untraced": round_s[0], "traced": round_s[1],
                    "untraced_after": round_s[2]},
        "layers_ms_per_round": {k: v / 1e6 / n
                                for k, v in per_layer(own).items()},
        "own_ms_total": sum(own.values()) / 1e6,
        "self_times_ms_total": self_ns / 1e6,
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "ops": n_ops, "ops_without_path": sum(no_path.values()),
        "ops_without_path_by_kind": no_path.most_common(TOP),
        "unscoped_ops_ms_per_round": [[name, t / 1e6 / n]
                                      for t, name in unscoped],
        "idle_gaps": reduced["idle_gaps"],
        "sgd_steps": {"real": real, "computed": computed,
                      "real_frac_pct": 100.0 * real / computed
                      if computed else None},
    }
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    HERE = Path(__file__).resolve().parent
    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    sys.exit(main())
