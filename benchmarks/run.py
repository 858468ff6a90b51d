"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run fig3 table3  # subset
"""

from __future__ import annotations

import sys
import time

from .common import Csv


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from . import (decode_bench, drift, failover, fig3_dot_error,
                   fig4_overflow, fig5_markov, fig9_pareto, kernel_bench,
                   replica_throughput, roofline_table, serving_bench,
                   spec_bench, table1_accuracy, table3_energy)
    suites = {
        "fig3": fig3_dot_error.run,
        "fig4": fig4_overflow.run,
        "fig5": fig5_markov.run,
        "fig9": fig9_pareto.run,
        "table1": table1_accuracy.run,
        "table3": table3_energy.run,
        "kernel": kernel_bench.run,
        "roofline": roofline_table.run,
        "replica": replica_throughput.run,
        "decode": decode_bench.run,
        "failover": failover.run,
        "drift": drift.run,
        "serving": serving_bench.run,
        "spec": spec_bench.run,
    }
    want = sys.argv[1:] or list(suites)
    csv = Csv()
    print("name,us_per_call,derived")
    for name in want:
        t0 = time.time()
        suites[name](csv)
        csv.add(f"{name}/_suite_wall", (time.time() - t0) * 1e6, "ok")
    csv.dump()


if __name__ == "__main__":
    main()
