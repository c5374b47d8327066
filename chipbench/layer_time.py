"""Device time per train step of each layer of the program, and the
host's lag in seeing each step end, from a traced window.

The program names its layers with ``jax.named_scope`` (names from
``repro.core.scopes``), and XLA keeps each instruction's scope path as
``metadata={op_name=...}`` in the compiled text.  After the window,
``layer_ms`` builds the cell's step again (the runner's ``build``: the
same jit and shardings, so the same instruction names), maps each instruction of its text to a layer (``layer_table``),
and applies that to the self times of the ops that ran inside the
window's runs of the step program (``lm_train_step``).  Each layer's
seconds over those runs, summed over devices, divided by the runs, is
its milliseconds per step; the layers and ``unscoped`` add up to the op
self time of the runs.

The mapping is part of this yardstick, so it lives here and not in the
program: an op's layer is the innermost known scope in its ``op_name``;
a fusion takes its own name's layer, else its root's, else its first
fused instruction's, and one whose names span two layers counts as
mixed (``train.mixed_fusion_pct``); an instruction the compiler added
without a name takes its first reader's layer, else its first operand's.

A program without the scopes or without a step of that name (an older
commit) reads nothing: every reader returns None.  So does a window in
which instructions missing from the table hold over 1% of op time.
"""

from __future__ import annotations

import ast
import bisect
import re
import statistics
import time
from collections import defaultdict
from pathlib import Path

from chipbench import trace as TRC

STEP = "lm_train_step"
SYNC = "fit.sync"
MISSING_LIMIT = 0.01


def declared(directory: Path) -> tuple:
    """The scope names that the readers in ``directory`` declare, each in
    a top-level ``SCOPES = (...)``, read without running the readers."""
    out = []
    for path in sorted(directory.glob("*.py")):
        for node in ast.parse(path.read_text()).body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and getattr(node.targets[0], "id", None) == "SCOPES"):
                out += [s for s in ast.literal_eval(node.value) if s not in out]
    return tuple(out)


# the scope names of repro.core.scopes, held here so that the yardstick
# does not move with the program: those of every train step, then any
# that a reader of metrics/ declares (tests/test_scopes.py holds them
# equal to the program's)
BASE = ("ff", "bp", "wu", "attention", "moe_dispatch", "blocks",
        "embed_head", "update")
LAYERS = BASE + tuple(s for s in declared(Path(__file__).parent / "metrics")
                      if s not in BASE)
UNSCOPED = "unscoped"

_WORD = re.compile(r"\w+")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "opt-barrier",
             "after-all"}


def layer_of(op_name: str) -> str:
    """Layer of one ``op_name`` path; of a ``;``-separated list, the
    first path's."""
    path = op_name.split(";", 1)[0]
    for part in reversed(path.split("/")):
        # "transpose(jvp(blocks))" -> transpose, jvp, blocks: a wrapper
        # is never a layer name, so the last known word is the scope
        for word in reversed(_WORD.findall(part)):
            if word in LAYERS:
                return word
    return UNSCOPED


def layers_of(op_name: str) -> set:
    """Layers of every path in a ``;``-separated ``op_name`` list."""
    return {layer_of(p) for p in op_name.split(";") if p}


def layer_table(hlo_text: str) -> dict:
    """{instruction: (layer, mixed)} of every instruction of a compiled
    module's text (parsed by ``repro.launch.hlo_cost.parse_module``)."""
    from repro.launch.hlo_cost import parse_module

    comps = parse_module(hlo_text)
    out = {}
    for comp in comps.values():
        layer, users = {}, {}
        for op in comp.ops:
            names = [op.op_name] if op.op_name else []
            called = comps.get(_fused(op))
            if called is not None:
                root = called.root_op()
                names += [o.op_name for o in [root, *called.ops]
                          if o is not None and o.op_name]
            found = [lay for lay in map(layer_of, names) if lay != UNSCOPED]
            seen = set().union(*map(layers_of, names)) - {UNSCOPED}
            layer[op.name] = found[0] if found else UNSCOPED
            out[op.name] = len(seen) > 1
            for x in op.operands:
                users.setdefault(x, []).append(op.name)
        _inherit(comp.ops, layer, users)
        for op in comp.ops:
            out[op.name] = (layer[op.name], out[op.name])
    return out


def _inherit(ops, layer, users) -> None:
    """Give each unscoped instruction its readers' layer, else its
    operands', until nothing changes.  Tuples and parameters only pass
    values along, so they neither take nor give a layer."""
    kind = {op.name: op.kind for op in ops}
    changed = True
    while changed:
        changed = False
        for op in ops:
            if layer[op.name] != UNSCOPED or op.kind in _PLUMBING:
                continue
            for n in users.get(op.name, []) + op.operands:
                if (layer.get(n, UNSCOPED) != UNSCOPED
                        and kind.get(n) not in _PLUMBING):
                    layer[op.name] = layer[n]
                    changed = True
                    break


def _fused(op) -> str:
    m = _CALLS.search(op.line) if op.kind == "fusion" else None
    return m.group(1) if m else ""


def step_runs(tr, window, program: str = STEP) -> dict:
    """{device: [runs of ``program`` wholly inside the window]}."""
    lo, hi = window
    return {dev: [e for e in tr.modules.get(dev, [])
                  if TRC.program(e.name) == program
                  and lo <= e.start and e.end <= hi]
            for dev in tr.devices}


def inside(ops, runs) -> list:
    """The ops (sorted by start) that lie wholly inside one of ``runs``."""
    runs = sorted(runs, key=lambda e: e.start)
    starts = [r.start for r in runs]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= runs[i].end:
            out.append(e)
    return out


def split(tr, window, table: dict, program: str = STEP) -> dict:
    """Self seconds of the program's ops in the window's runs, by layer.

    ``table`` is ``{instruction: (layer, mixed)}``.  Returns
    ``{"layers": {layer: s}, "missing": {instruction: s}, "mixed_s",
    "runs", "program_s", "top": {layer: [(instruction, s)]}}``, seconds
    summed over devices."""
    runs = step_runs(tr, window, program)
    layers, missing, by_op = defaultdict(float), defaultdict(float), {}
    mixed = program_s = 0.0
    for dev, rs in runs.items():
        program_s += sum(e.end - e.start for e in rs)
        for name, s in TRC.self_times(inside(tr.ops[dev], rs)).items():
            if name not in table:
                missing[name] += s
                continue
            layer, mix = table[name]
            layers[layer] += s
            mixed += s if mix else 0.0
            by_op[name] = by_op.get(name, 0.0) + s
    top = defaultdict(list)
    for name, s in sorted(by_op.items(), key=lambda kv: -kv[1]):
        top[table[name][0]].append((name, s))
    return {"layers": dict(layers), "missing": dict(missing),
            "mixed_s": mixed, "runs": sum(len(r) for r in runs.values()),
            "program_s": program_s, "top": dict(top)}


def sync_lags(tr, window, program: str = STEP) -> list:
    """[(sync span start, lag s)] of each ``fit.sync`` span in the window:
    from the end of the step's run on the device (the last run that
    started before the span ended; the latest over devices) to the end
    of the span that waited for it."""
    lo, hi = window
    runs = {dev: sorted((e for e in tr.modules.get(dev, [])
                         if TRC.program(e.name) == program),
                        key=lambda e: e.start) for dev in tr.devices}
    starts = {dev: [e.start for e in rs] for dev, rs in runs.items()}
    out = []
    for s in sorted((e for e in tr.host if e.name == SYNC
                     and lo <= e.start and e.end <= hi),
                    key=lambda e: e.start):
        ends = [runs[d][i].end for d in runs
                for i in [bisect.bisect_left(starts[d], s.end) - 1] if i >= 0]
        if ends:
            out.append((s.start, s.end - max(ends)))
    return out


def _step_text(ctx, n_devices: int):
    """Compiled text of the cell's step, built again on the traced
    devices.

    The compile cache leaves debug info, and so the scopes, out of its
    key: an executable cached from the same computation under other
    scope names would bring those names.  So the rebuild keys the cache
    with the metadata in; the first traced run on a machine compiles
    afresh."""
    import jax

    from chipbench import spec

    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    t0 = time.perf_counter()
    try:
        b = spec.runner(ctx["mix"]["kind"]).build(
            ctx["conf"], ctx["mix"], ctx["log"], jax.devices()[:n_devices])
    finally:
        jax.config.update(flag, was)
    ctx["log"](f"layer time: step built again in "
               f"{time.perf_counter() - t0:.1f} s")
    return b["bundle"].step_fn.as_text()


def _layer_ms(ctx):
    log = ctx["log"]
    tr, win = ctx["trace"], ctx["window"]
    if not any(step_runs(tr, win).values()):
        log(f"layer time: no run of {STEP} inside the window")
        return None
    table = layer_table(_step_text(ctx, len(tr.devices)))
    if all(lay == UNSCOPED for lay, _ in table.values()):
        log("layer time: the step's text names no scope; nothing to read")
        return None
    sp = split(tr, win, table)
    runs, layers = sp["runs"], sp["layers"]
    op_s = sum(layers.values()) + sum(sp["missing"].values())
    per = {k: 1e3 * v / runs for k, v in sorted(layers.items(),
                                                key=lambda kv: -kv[1])}
    log(f"layer time: {runs} runs of {STEP}; ms per step by layer "
        f"{ {k: round(v, 3) for k, v in per.items()} }; op self time "
        f"{1e3 * op_s / runs:.3f} ms of {1e3 * sp['program_s'] / runs:.3f} "
        f"ms of program a step (no op running "
        f"{1e3 * (sp['program_s'] - op_s) / runs:.3f} ms); fusions whose "
        f"names span two layers hold {100 * sp['mixed_s'] / op_s:.2f}%")
    for layer, ops in sp["top"].items():
        k = 5 if layer in (UNSCOPED, "blocks") else 3
        log(f"layer time: longest {layer} instructions (ms a step) "
            f"{[(n, round(1e3 * s / runs, 3)) for n, s in ops[:k]]}")
    if sp["missing"]:
        worst = sorted(sp["missing"].items(), key=lambda kv: -kv[1])[:10]
        log(f"layer time: {len(sp['missing'])} instructions not in the "
            f"table hold {100 * sum(sp['missing'].values()) / op_s:.3f}% "
            f"of op time: {worst}")
        if sum(sp["missing"].values()) > MISSING_LIMIT * op_s:
            return None
    per["_op"] = 1e3 * op_s / runs
    per["_mixed"] = 1e3 * sp["mixed_s"] / runs
    return per


def layer_ms(ctx):
    """{layer: ms per step}, plus ``_op`` (all op self time per step) and
    ``_mixed`` (that of fusions spanning two layers), or None; computed
    once per traced run and kept in ``ctx``."""
    if "layer_ms" not in ctx:
        ctx["layer_ms"] = _layer_ms(ctx)
    return ctx["layer_ms"]


def read_layer(ctx, layer: str):
    per = layer_ms(ctx)
    return None if per is None else per.get(layer, 0.0)


def read_sync_lag_ms(ctx):
    """Mean lag per step in ms; logs the largest and the three worst."""
    lags = [lag for _, lag in sync_lags(ctx["trace"], ctx["window"])]
    if not lags:
        return None
    worst = sorted(range(len(lags)), key=lambda i: -lags[i])[:3]
    ctx["log"](f"sync lag: {len(lags)} steps, mean "
               f"{1e3 * statistics.mean(lags):.3f} ms, median "
               f"{1e3 * statistics.median(lags):.3f} ms, max "
               f"{1e3 * max(lags):.3f} ms; worst (index in the window, ms): "
               f"{[(i, round(1e3 * lags[i], 3)) for i in worst]}")
    return 1e3 * statistics.mean(lags)
