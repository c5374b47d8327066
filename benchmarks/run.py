"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table2     # one
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI-fast subset
"""

from __future__ import annotations

import inspect
import sys
import time

from benchmarks import (fig14_resources, fig15_speedup, fig16_layerwise,
                        fig17_scaling, fleet_bench, kernel_bench,
                        pregen_bench, serve_bench, spmd_bench,
                        table2_flops, table4_platforms, table5_accels)

SUITES = {
    "table2": table2_flops,
    "fig14": fig14_resources,
    "fig15": fig15_speedup,
    "fig16": fig16_layerwise,
    "table4": table4_platforms,
    "fig17": fig17_scaling,
    "table5": table5_accels,
    "kernels": kernel_bench,
    "serve": serve_bench,
    # fleet layer above the engine: KV-aware routing + disaggregation
    "fleet": fleet_bench,
    # pre-generation dataflow gate: exactly one top_k per prunable param
    "pregen": pregen_bench,
    # needs multiple devices to be interesting; run it standalone with
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI spmd
    # job does) — inside this driver it inherits the ambient backend
    "spmd": spmd_bench,
}

# cheap suites CI can afford on every push
SMOKE_SUITES = ["table2", "serve", "fleet", "pregen"]


def main() -> None:
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    names = [a for a in argv if not a.startswith("-")]
    if not names:
        names = SMOKE_SUITES if smoke else list(SUITES)
    for name in names:
        mod = SUITES[name]
        print(f"\n===== {name} ({mod.__name__}) =====")
        t0 = time.perf_counter()
        kwargs = {}
        if "smoke" in inspect.signature(mod.main).parameters:
            kwargs["smoke"] = smoke  # suites opt in by accepting smoke=
        mod.main(**kwargs)
        print(f"# {name}: {(time.perf_counter() - t0)*1e3:.0f} ms")


if __name__ == "__main__":
    main()
