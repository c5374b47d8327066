#!/usr/bin/env python3
"""Readings that the limits of ``cells/<workload>.json`` are set from.
Not run by the benchmark's own runs.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,.. \
        [--control-seeds a,b,c]

For each seed the program's first steps are compared with the reference
as a benchmark run compares them (the lower readings).  For each control
seed the reference computed one precision below the configuration's (the
family's control) takes the program's place, and so does the reference
on half of each batch (a planted fault).  One JSON line per reading on
stdout and in ``<--out>/calib_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import spec  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(out: Path, rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    with open(out, "a") as f:
        f.write(line + "\n")


def readings(conf, mix, seeds, control, out, devices) -> None:
    TC = spec.runner(mix["kind"])
    b = TC.build(conf, mix, log, devices)
    for seed in seeds:
        st = TC.first_steps(b, seed, log)
        first = st["first"]
        del st
        gc.collect()
        t0 = time.perf_counter()
        ref = TC.reference_readings(conf, mix, seed)
        got = TC.compare(first, ref)
        emit(out, {"seed": seed, "what": "program", "ref_s": time.perf_counter() - t0,
                   **got})
        if seed in control:
            for what, kw in (("control", {"control": True}),
                             ("fault_half_batch", {"drop_half": True})):
                other = TC.reference_readings(conf, mix, seed, **kw)
                emit(out, {"seed": seed, "what": what, **TC.compare(other, ref)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=str(REPO / "chipbench" / "_out"))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])

    import jax

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"calibrate: needs {cell['chips']} TPU chip(s)")
        return 3
    out = Path(args.out) / f"calib_{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    readings(conf, mix, seeds, control, out, devices[:cell["chips"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
