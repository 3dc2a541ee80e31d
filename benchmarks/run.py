# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# The first benchmark that fails stops the run with its traceback.
from __future__ import annotations


def main() -> None:
    from benchmarks import (
        family_search,
        faults_bench,
        fig5_batch_sweep,
        multitenant_bench,
        obs_bench,
        paged_attn_bench,
        serve_sweep,
        spec_decode_bench,
        table2_parallel_modes,
        table5_utilization,
        table6_stage_perf,
        table7_comparison,
    )

    print("name,us_per_call,derived")
    for mod in (
        table2_parallel_modes,
        table5_utilization,
        table6_stage_perf,
        table7_comparison,
        fig5_batch_sweep,
        serve_sweep,
        paged_attn_bench,
        spec_decode_bench,
        multitenant_bench,
        faults_bench,
        family_search,
        obs_bench,
    ):
        mod.run()


if __name__ == "__main__":
    main()
