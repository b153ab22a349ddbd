# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows: Fig. 2 (PPA model accuracy), Figs. 3-5 (DSE Pareto + headline
# ratios), kernel micro-benches, and the §Roofline table from the dry-run.
import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (accuracy_bench, coexplore_bench,
                            coexplore_many_bench, dse_sweep_bench,
                            fig2_ppa_accuracy, fig3to5_dse, kernel_bench,
                            quant_accuracy, roofline_bench,
                            serving_dse_bench)
    modules = [
        ("fig2", fig2_ppa_accuracy),
        ("fig3to5", fig3to5_dse),
        ("dse_sweep", dse_sweep_bench),
        ("coexplore", coexplore_bench),
        ("coexplore_many", coexplore_many_bench),
        ("serving_dse", serving_dse_bench),
        ("accuracy", accuracy_bench),
        ("kernels", kernel_bench),
        ("quant_acc", quant_accuracy),
        ("roofline", roofline_bench),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for tag, mod in modules:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:
            failures += 1
            print(f"{tag}/EXCEPTION,0.00,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    # end-of-run telemetry: accumulated registry counters across every
    # bench above (cache hit rates, configs/s, evals/s) — stderr so the
    # CSV on stdout stays machine-parseable
    from repro.obs import render_text
    print(render_text(), file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
