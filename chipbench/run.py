#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); the mix's ``kind`` picks the
module that runs it (``chipbench/<kind>_cell.py``), and the
configuration's ``family`` the module that maps it to the program and
holds its reference (``chipbench/families/<family>.py``).  Set-up builds
the program's objects from the seed on the cell's chips, warms every
shape the window uses and drives the first steps that the reference
will follow; it ends by collecting and freezing the set-up's garbage, so
that no collection of it falls in the window.  The window then runs for
``--seconds``; after it the reference decides ``correct``.

With ``--trace 0`` the result reports the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read by
``chipbench/metrics/<metric>.py`` from a profiler trace of the window and
the harness's own counters.  The last line of stdout is one JSON object;
the last lines of stderr are the numbers compared, each with its limit.
Exits 3 without a result when JAX finds no TPU or too few chips.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import spec  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int):
    """The first ``chips`` TPUs JAX sees, or None when it sees fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"chipbench: needs {chips} TPU chip(s), JAX finds "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    return devs[:chips]


class CompileCounter:
    """Counts backend compilations while ``on``."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class GCWatch:
    """Pauses of Python's cyclic garbage collector while ``on``:
    [(generation, seconds)]."""

    def __init__(self):
        self.on, self.pauses, self._t0 = False, [], None
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))
            self._t0 = None


class Tracer:
    """The profiler around the window, when ``--trace 1``."""

    WINDOW = "chipbench.window"

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_") if enabled else None
        self._ann = None

    def __enter__(self):
        if self.enabled:
            import jax

            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(self.WINDOW)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def reduce(self):
        """(trace, (window start, end) in trace time)."""
        from chipbench import trace as TRC

        tr = TRC.load(TRC.find_file(self.dir))
        return tr, TRC.host_span(tr, self.WINDOW)

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for m in spec.cell_metrics(bench, workload, trace=True):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, bench: dict, cell: dict, conf: dict, mix: dict, devices) -> dict:
    """Drive one run; returns the result object (without printing it)."""
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    runner = spec.runner(mix["kind"])
    limits = spec.limits(cell["name"])
    counter = CompileCounter()
    gcw = GCWatch()
    tracer = Tracer(bool(args.trace))
    try:
        state = runner.setup(conf, mix, args.seed, log, devices)
        t0 = time.perf_counter()
        gc.collect()
        gc.freeze()
        log(f"set-up garbage collected in {time.perf_counter() - t0:.3f} s; "
            f"{gc.get_freeze_count()} objects frozen")
        setup_s = time.perf_counter() - T_START
        counter.on = gcw.on = True
        with tracer:
            res = runner.window(state, args.seconds, log)
        counter.on = gcw.on = False
        gc.unfreeze()
        log(f"garbage collections in the window: {len(gcw.pauses)}, by "
            f"generation {[sum(g == k for g, _ in gcw.pauses) for k in range(3)]}, "
            f"longest {max((p for _, p in gcw.pauses), default=0.0):.4f} s")
        stats = devices[0].memory_stats() or {}
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        log(f"set-up {setup_s:.3f} s, window {res['window_s']:.3f} s, "
            f"compilations in the window: {counter.count}, peak bytes in "
            f"use {peak} of {stats.get('bytes_limit')}")
        traced = {}
        if args.trace:
            from chipbench import trace as TRC

            tr, win = tracer.reduce()
            for dev in tr.devices:
                runs = [e for e in tr.modules[dev] if win[0] <= e.start < win[1]]
                gaps = [b.start - a.end for a, b in zip(runs, runs[1:])]
                log(f"{dev}: {len(runs)} program runs in the window, longest "
                    f"{max((e.end - e.start for e in runs), default=0.0):.4f} s, "
                    f"longest gap between runs {max(gaps, default=0.0):.4f} s")
            ctx = {"trace": tr, "window": win, "window_s": win[1] - win[0],
                   "busy_s": TRC.busy_s(tr, win), "res": res, "mix": mix,
                   "conf": conf, "peak_bytes": peak,
                   "peaks": spec.peaks(devices[0].device_kind), "log": log}
            traced = {
                "metrics": per_layer(bench, cell["name"], ctx),
                "busy_s": ctx["busy_s"], "window_s": ctx["window_s"],
                "breakdown": {
                    "device_ops": TRC.top_ops(tr, 10, win),
                    "idle_gaps": TRC.idle_gaps(tr, win, 10,
                                               skip={Tracer.WINDOW})}}
            del tr, ctx
        checks = runner.check(state, res, limits, log)
    finally:
        tracer.close()
    correct = (res["failed"] == 0 and counter.count == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if args.trace:
        out["metrics"] = traced["metrics"]
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = traced["breakdown"]
    else:
        e2e = {**res["metrics"], "setup_s": setup_s}
        for m in spec.cell_metrics(bench, cell["name"], trace=False):
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    if counter.count:
        log(f"{counter.count} compilation(s) inside the window")
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = device_info(cell["chips"])
    if devices is None:
        return 3
    out = run(args, bench, cell, conf, mix, devices)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    log(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
