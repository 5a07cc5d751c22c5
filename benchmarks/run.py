# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations


def main() -> None:
    from benchmarks import (fig2_variance, retrieval_microbench,
                            roofline_report, service_throughput,
                            table1_accuracy, table2_tokens, table3_categories)
    rows = []
    for mod in (table1_accuracy, table2_tokens, table3_categories,
                fig2_variance, retrieval_microbench, service_throughput,
                roofline_report):
        rows = mod.run(rows)
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.common.utils import init_compilation_cache
    init_compilation_cache()
    main()
