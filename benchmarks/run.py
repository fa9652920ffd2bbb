# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows:
#   fig4_accuracy/*   — paper Fig. 4 (global model accuracy per strategy)
#   fig5_loss/*       — paper Fig. 5 (loss per strategy)
#   fig6_comm_cost/*  — paper Fig. 6 (normalized communication cost)
#   fig7_exec_time/*  — paper Fig. 7 (normalized execution time)
#   kernel/*          — Pallas kernel micro-benchmarks
import sys
import traceback


def main() -> None:
    from benchmarks.fl_bench import (bench_accuracy, bench_comm_cost,
                                     bench_exec_time, bench_loss,
                                     bench_noniid_ablation)
    from benchmarks.kernel_bench import bench_kernels
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    benches = [bench_kernels, bench_accuracy, bench_loss, bench_comm_cost,
               bench_exec_time, bench_noniid_ablation]
    print("name,us_per_call,derived")
    failures = 0
    for bench in benches:
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
