"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Device planes are ``/device:TPU:<n>``: their ``XLA Ops`` line holds one
event per executed HLO instruction (a loop's instruction spans the ops
it runs, so events nest), their ``XLA Modules`` line one event per
program run, named ``jit_<function>(<hash>)``.  Host threads are lines of
``/host:CPU``.  Times are kept in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane -> [Event], XLA Ops
    modules: dict      # device plane -> [Event], XLA Modules
    host: list         # [Event] of every host thread

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def find_file(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {files}")
    return files[0]


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = sorted((Event(e.name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events),
                                 key=lambda e: (e.start, -e.end))
                    (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events)
    for dev in modules:
        ops.setdefault(dev, [])
    return Trace(ops, modules, host)


def instruction(op_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return op_name.split(" = ", 1)[0].lstrip("%").strip()


def kernel(op_name: str) -> str:
    """Instruction name without its numeric suffix: ``nm_spmm_2_8_u4``."""
    return re.sub(r"\.\d+$", "", instruction(op_name))


def program(module_name: str) -> str:
    """``jit_decode_fn(1234)`` -> ``decode_fn``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _clip(events, window):
    lo, hi = window if window else (float("-inf"), float("inf"))
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield s, t


def union(intervals) -> list:
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_s(tr: Trace, window=None) -> float:
    """Seconds in which some operation ran, averaged over devices."""
    per = [sum(t - s for s, t in union(_clip(tr.ops[d], window)))
           for d in tr.devices]
    return sum(per) / len(per) if per else 0.0


def program_time(tr: Trace, name: str, window=None) -> tuple:
    """(seconds, runs) of one jitted program, summed over devices."""
    secs, runs = 0.0, 0
    for evs in tr.modules.values():
        for e in evs:
            if program(e.name) == name:
                for s, t in _clip([e], window):
                    secs += t - s
                    runs += 1
    return secs, runs


def program_times(tr: Trace, window=None) -> dict:
    """{program: (seconds, runs)} of every jitted program that ran in the
    window, summed over devices."""
    out = defaultdict(lambda: [0.0, 0])
    for evs in tr.modules.values():
        for e in evs:
            for s, t in _clip([e], window):
                out[program(e.name)][0] += t - s
                out[program(e.name)][1] += 1
    return {k: tuple(v) for k, v in out.items()}


def kernel_time(tr: Trace, pattern: str, window=None) -> tuple:
    """(seconds, calls) of the ops whose kernel name matches ``pattern``."""
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for evs in tr.ops.values():
        for e in evs:
            if rx.fullmatch(kernel(e.name)):
                for s, t in _clip([e], window):
                    secs += t - s
                    calls += 1
    return secs, calls


def self_times(events) -> dict:
    """{instruction: seconds} with the time of nested ops taken out of
    the op that contains them."""
    out = defaultdict(float)
    stack = []   # [name, end, child_time]

    def close(entry):
        name, start, end, child = entry
        out[name] += (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for e in events:   # sorted by start, longest first
        while stack and stack[-1][2] <= e.start:
            close(stack.pop())
        stack.append([instruction(e.name), e.start, e.end, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def top_ops(tr: Trace, k: int = 10, window=None) -> list:
    """The ``k`` instructions with the most self time, [[name, s]],
    summed over devices."""
    total = defaultdict(float)
    for evs in tr.ops.values():
        clipped = [Event(e.name, s, t) for e in evs
                   for s, t in _clip([e], window)]
        for name, s in self_times(clipped).items():
            total[name] += s
    return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, window, k: int = 10, skip=()) -> list:
    """The ``k`` longest device-idle gaps inside ``window``, each named by
    what the host was doing in it: the host event name with the most
    time inside the gap, summed over its events, of events shorter than
    half the window (so not the window's own span or its caller).
    [[name, s]], on the first device."""
    if not tr.devices:
        return []
    busy = union(_clip(tr.ops[tr.devices[0]], window))
    lo, hi = window
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in tr.host
            if e.name not in skip and e.end - e.start < 0.5 * (hi - lo)]
    out = []
    for s, t in gaps[:k]:
        inside = defaultdict(float)
        for e in host:
            ov = min(t, e.end) - max(s, e.start)
            if ov > 0:
                inside[e.name] += ov
        out.append([max(inside, key=inside.get) if inside else "no host event",
                    t - s])
    return out


def host_span(tr: Trace, name: str):
    """(start, end) of the first host event called ``name``."""
    for e in sorted(tr.host, key=lambda e: e.start):
        if e.name == name:
            return e.start, e.end
    raise KeyError(f"no host event {name!r} in the trace")
