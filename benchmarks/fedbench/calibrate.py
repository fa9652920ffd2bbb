#!/usr/bin/env python3
"""Readings that the check's limits are set from, for one cell.

    python3 benchmarks/fedbench/calibrate.py --workload cnn_fedbwo_paper \
        --seeds 101-112 --control 101-103 --faults 101-103 [--out FILE] \
        [--traffic '{"noise": 4.0}']

For each seed, in one process: the program's first rounds at the cell's
size (as a benchmark run's set-up drives them) against the float32
reference; on ``--control`` seeds the control, the reference computed in
bfloat16 (weights and floating inputs; token ids and labels stay
integers) in the program's place; on ``--faults`` seeds the reference
with the second half of every training batch (along the leading batch
axis of every array) left out in the program's place.  Every
number of ``check`` is printed for each, one JSON line per run.  No
measured window is needed.  Needs a TPU, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import gc                                                     # noqa: E402
import json                                                   # noqa: E402
import sys                                                    # noqa: E402
from pathlib import Path                                      # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def seed_list(text: str):
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


def readings(cell, seed, control: bool, fault: bool):
    import jax.numpy as jnp
    from fedbench import harness
    t0 = time.perf_counter()
    p = harness.prepare(cell, seed)
    logs = p.first.logs
    exact = harness.exact_counts(p.exp.server, p.traffic, p.proto, logs)
    p.exp = p.eval_data = None
    gc.collect()
    t1 = time.perf_counter()
    ref = harness.reference(p)
    ref_run = harness.follow(ref, p.first)
    t2 = time.perf_counter()
    scores = ref_run.logs[0]["scores"]
    rows = [dict(kind="program", seed=seed, prepare_s=t1 - t0,
                 reference_s=t2 - t1, **exact,
                 ref_score_r0=[float(scores.min()), float(scores.max())],
                 ref_eval_loss=[l["eval_loss"] for l in ref_run.logs],
                 ref_eval_acc=[l["eval_acc"] for l in ref_run.logs],
                 **harness.judge(p.first, ref, ref_run, p.proto.is_fedx))]
    runs = []
    if control:
        runs.append(("control_bf16", dict(dtype=jnp.bfloat16,
                                          precision=None)))
    if fault:
        runs.append(("fault_half_batch", dict(fault="half_batch")))
    for kind, kw in runs:
        t3 = time.perf_counter()
        other = harness.reference(p, **kw)
        run = harness.follow(other, p.first)
        del other
        rows.append(dict(kind=kind, seed=seed,
                         run_s=time.perf_counter() - t3,
                         **harness.judge(run, ref, ref_run,
                                         p.proto.is_fedx)))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--traffic", default="{}",
                    help="JSON object merged into the cell's traffic")
    args = ap.parse_args(argv)

    from fedbench import harness, spec
    try:
        harness.device_check(1)
    except harness.NoChip as e:
        harness.say(e)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    harness.say(f"compile cache {enable_compile_cache()}")
    cell = spec.workload(args.workload)
    cell["traffic"].update(json.loads(args.traffic))
    control, faults = set(seed_list(args.control)), set(seed_list(args.faults))
    out = open(args.out, "a") if args.out else None
    try:
        for seed in seed_list(args.seeds):
            for row in readings(cell, seed, seed in control, seed in faults):
                line = json.dumps(row)
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
            gc.collect()
    finally:
        if out:
            out.close()
    harness.say(f"total {time.perf_counter() - T_START:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
