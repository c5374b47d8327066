#!/usr/bin/env python3
"""Bring-up check on the chip: granite-moe-1b-a400m through the BDWP
trainer and the packed serve engine, each held against a reference.

    python3 chip_smoke.py [--seed 0]     # one chip: kernels, train, serve
    python3 chip_smoke.py --chips 4      # four chips: sharded train, fleet

One chip runs three phases:
  kernels  every Pallas kernel at granite widths against its jnp oracle;
  train    published widths, depth cut to 4 layers, BDWP 2:8, a synthetic
           8 x 1024 batch: four steps of ``trainer.fit`` on the default
           path (pregen, unpacked operands), then on the kernel path
           (packed FF through nm_spmm, fused_update weight update);
  serve    full depth, u4-packed 2:8 ``ServeEngine``: six requests, one
           joining mid-flight, each stream against its solo decode, and
           packed prefill logits against a masked-dense engine.
With ``--chips 4`` it runs only the 4-layer train step on a
``pod=2,data=1,model=2`` mesh and a four-replica full-depth
``ServeFleet``.  Dense pod sync is held against the one-chip run;
compressed pod sync against a run of the same semantics written out
without a pod axis (``two_pod_reference``, on two of the chips).

Weights are random, made from ``--seed``.  The script refuses to run
anywhere but a TPU.  Every check that fails raises; the last line of
stdout is one JSON object, printed only when all of them passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.analysis.graph_audit import pallas_call_census  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import operand as O  # noqa: E402
from repro.core.sparsity import SparsityConfig, nm_pack, pack_idx_u4  # noqa: E402
from repro.data import synthetic as D  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.nm_spmm_shared import decompress_nm  # noqa: E402
from repro.launch import spmd  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import transformer_lm as T  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.serve import ServeConfig, ServeEngine  # noqa: E402
from repro.serve.fleet import FleetConfig, ServeFleet  # noqa: E402
from repro.train import step as ST  # noqa: E402
from repro.train import trainer as TR  # noqa: E402

ARCH = "granite-moe-1b-a400m"
SP = SparsityConfig(n=2, m=8, method="bdwp")
TRAIN_LAYERS = 4          # full depth (24) needs ~17 B/param: > 16 GB
BATCH, SEQ, STEPS = 8, 1024, 4
# one chip: constant lr from step 0, so the masks and packed operands
# the fused weight update writes change under both paths.  At 0.05 the
# loss climbs from step 2 on (11.4 -> 35 in four steps on TPU v5e);
# at 0.01 it ends below its start (11.44, 9.52, 10.13, 10.04 on v5e).
OPT = sgd.SGDConfig(lr=0.01, warmup_steps=0, total_steps=STEPS,
                    min_lr_frac=1.0)
# four chips: the launcher's schedule (lr 0.1, 100-step linear warmup:
# the updates after steps 0..2 have lr 0, 1e-3, 2e-3; the one-chip loss
# still moves 11.44 -> 10.01 over four steps on TPU v5e)
OPT_SHARDED = sgd.SGDConfig(lr=0.1, total_steps=STEPS)
SLOTS, BUCKET, MAX_NEW = 4, 128, 32
PROMPT_LENS = (16, 128, 47, 90, 23, 111)
MAX_NEWS = (32, 12, 32, 24, 32, 20)
NM_SPMM = r"nm_spmm_\d+_\d+(_u4)?"
# kernel-phase shapes, all granite widths: (d_model, d_expert) weight,
# decode and expert-capacity activation rows, the stacked w_down leaf
# with its FF axis last, a (4096, 12288) compaction, a 2-pod 1M-element
# gradient slab, and a shared-mode activation panel
KERNEL_SHAPES = {"weight": (1024, 512), "act_rows": (SLOTS, 2560),
                 "leaf": (32 * 1024, 512), "compact": (4096, 12288),
                 "slab": (2, 1 << 20), "shared_rows": 256}

# Tolerances, each about ten times the gap measured on TPU v5e.  The
# paths compared compute the same math and differ in f32 summation
# order (Mosaic's K-tiled accumulation against XLA's dot, a TP-sharded
# contraction against a whole one); a last-bit change in a sum can flip
# the bf16 rounding of an activation, and later steps also a top-8
# routing choice or a near-tie N:M survivor.
FIRST_RTOL = 2.5e-4       # kernel vs default path, step 0 (2.4e-5)
TRACK_RTOL = 2e-3         # kernel vs default path, every step (2.0e-4)
SYNC_RTOL = 4e-4          # pod=2,model=2 vs its one-chip reference, every
                          # step (dense sync: 3.8e-5)
LOGIT_RTOL = 2e-2         # max |packed - masked| / max |masked| logit
SPMM_RTOL = 1e-4          # kernel vs XLA f32-accumulated matmul


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def check_tracks(got, want, what: str, first=FIRST_RTOL,
                 every=TRACK_RTOL) -> None:
    ok = (np.isclose(got[0], want[0], rtol=first, atol=0)
          and np.allclose(got, want, rtol=every, atol=0))
    check(ok, f"{what}: losses {got} vs {want} (step 0 rtol {first}, "
              f"all steps rtol {every})")


def check_sync(got, want, what: str, rtol=SYNC_RTOL) -> None:
    check_tracks(got, want, what, first=rtol, every=rtol)


def _diff(a, b) -> int:
    """Elements where two same-shape arrays differ bitwise (NaN == NaN)."""
    a, b = np.asarray(a), np.asarray(b)
    return int((~((a == b) | ((a != a) & (b != b)))).sum())


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel against its oracle at granite widths
# ---------------------------------------------------------------------------


def kernels_phase(seed: int) -> dict:
    n, m = SP.n, SP.m
    shapes = KERNEL_SHAPES
    d, f = shapes["weight"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = jax.random.normal(keys[0], (d, f), jnp.float32) * d ** -0.5
    vals, idx = nm_pack(w.astype(jnp.bfloat16), n, m, axis=0)
    idx4 = pack_idx_u4(idx, axis=0)
    out = {}
    for rows in shapes["act_rows"]:
        act = jax.random.normal(keys[1], (rows, d), jnp.bfloat16)
        want = ops.nm_spmm(act, vals, idx, n, m, use_pallas=False)
        got8 = ops.nm_spmm(act, vals, idx, n, m)
        got4 = ops.nm_spmm(act, vals, idx4, n, m, idx_bits=4)
        err = _rel_err(got8, want)
        check(err <= SPMM_RTOL, f"nm_spmm rows={rows}: rel err {err}")
        bad = _diff(got4, got8)
        check(bad == 0, f"nm_spmm rows={rows}: u4 != u8 at {bad} elements")
        out[f"nm_spmm_rel_err_rows{rows}"] = err

    leaf = shapes["leaf"]
    wl = jax.random.normal(keys[2], leaf, jnp.float32) * 0.03
    gl = jax.random.normal(keys[3], leaf, jnp.float32) * 1e-3
    vl = jnp.zeros(leaf, jnp.float32)
    hyper = (0.05, 0.9, 5e-4, SP.lam)
    kern = ops.fused_update(wl, gl, vl, *hyper, n, m)
    orac = ops.fused_update(wl, gl, vl, *hyper, n, m, use_pallas=False)
    for name, a, b in zip(("w", "v", "vals", "idx"), kern, orac):
        bad = _diff(a, b)
        check(bad == 0, f"fused_update {leaf}: {name} differs from the "
                        f"oracle at {bad} elements")

    x = jax.random.normal(keys[4], shapes["compact"], jnp.bfloat16)
    for bits in (8, 4):
        kv, ki = ops.nm_compact(x, n, m, idx_bits=bits)
        ov, oi = ops.nm_compact(x, n, m, idx_bits=bits, use_pallas=False)
        bad = _diff(kv, ov) + _diff(ki, oi)
        check(bad == 0, f"nm_compact u{bits}: {bad} elements != oracle")

    g = jax.random.normal(keys[5], shapes["slab"], jnp.float32)
    e = g[::-1] * 0.1
    kc_ = ops.grad_compress(g, e, n, m)
    oc_ = ops.grad_compress(g, e, n, m, use_pallas=False)
    bad = sum(_diff(a, b) for a, b in zip(kc_, oc_))
    check(bad == 0, f"grad_compress: {bad} elements != jnp path")
    bad = _diff(ops.grad_decompress_mean(*kc_[:2], n, m),
                ops.grad_decompress_mean(*kc_[:2], n, m, use_pallas=False))
    check(bad == 0, f"grad_decompress_mean: {bad} elements != jnp path")

    sv, rows_ = ops.pack_shared(w, n, m)
    act = jax.random.normal(keys[1], (shapes["shared_rows"], d),
                            jnp.bfloat16)
    err = _rel_err(ops.nm_spmm_shared(act, sv, rows_),
                   ops.nm_spmm_shared(act, sv, rows_, use_pallas=False))
    check(err <= SPMM_RTOL, f"nm_spmm_shared: rel err {err}")
    out["nm_spmm_shared_rel_err"] = err
    log(f"[kernels] all six kernels match their oracles: {out}")
    return out


# ---------------------------------------------------------------------------
# train: trainer.fit on the default path and on the kernel path
# ---------------------------------------------------------------------------


def train_run(cfg, mesh, seed: int, label: str, *, opt=OPT, pack=False,
              use_pallas=False, compress=False, grad_sync=None, batch=BATCH,
              seq=SEQ, steps=STEPS) -> dict:
    """``steps`` of ``trainer.fit`` through ``build_lm_train``; returns
    the losses, compile seconds, and the nm_spmm census of the step."""
    opt = dataclasses.replace(opt, total_steps=steps)
    bundle = ST.build_lm_train(cfg, mesh, SP, opt, pregen_pack=pack,
                               use_pallas=use_pallas, compress=compress,
                               grad_sync=grad_sync)
    init = jax.jit(partial(ST.init_train_state, cfg=cfg, sp_cfg=SP,
                           pregen_pack=pack, compress=compress, mesh=mesh),
                   out_shardings=bundle.state_shardings)
    state = init(jax.random.PRNGKey(seed))
    batch_sh = {k: NamedSharding(mesh, ps)
                for k, ps in bundle.input_pspecs.items()}
    stream = D.lm_stream(cfg.vocab, batch, seq, shardings=batch_sh,
                         seed=seed)
    _, first = next(D.lm_stream(cfg.vocab, batch, seq, shardings=batch_sh,
                                seed=seed))
    census = pallas_call_census(bundle.step_fn, state, first, kernel=NM_SPMM)
    t0 = time.perf_counter()
    compiled = bundle.step_fn.lower(state, first).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    state, history = TR.fit(
        dataclasses.replace(bundle, step_fn=compiled), state, stream,
        TR.TrainerConfig(total_steps=steps, log_every=10 ** 9),
        log_fn=log)
    losses = [h["loss"] for h in history]
    n_packed = sum(isinstance(x, O.PregenOp) and x.is_packed
                   for x in jax.tree.leaves(
                       state["compute"],
                       is_leaf=lambda x: isinstance(x, O.PregenOp)))
    del state
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"[{label}] losses not finite: {losses}")
    res = {"losses": losses, "compile_s": compile_s,
           "step_s": [h["sec"] for h in history], "nm_spmm_calls": census,
           "packed_sites": n_packed, "peak_bytes_in_use": peak_bytes(),
           "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
           "temp_bytes": getattr(mem, "temp_size_in_bytes", None)}
    log(f"[{label}] " + json.dumps(res))
    return res


def train_phase(cfg, mesh, seed: int, **kw) -> dict:
    base = train_run(cfg, mesh, seed, "train default", **kw)
    kern = train_run(cfg, mesh, seed, "train kernels", pack=True,
                     use_pallas=True, **kw)
    # one call per packed site per forward pass; with remat the backward
    # pass re-runs each block's forward once more
    passes = 2 if cfg.remat else 1
    check(kern["nm_spmm_calls"] == passes * kern["packed_sites"] > 0,
          f"train kernel path: {kern['nm_spmm_calls']} nm_spmm calls for "
          f"{kern['packed_sites']} packed sites x {passes} forward passes")
    check_tracks(kern["losses"], base["losses"], "kernel vs default path")
    return {"default": base, "kernels": kern}


# ---------------------------------------------------------------------------
# serve: the packed engine against solo decode and a masked-dense engine
# ---------------------------------------------------------------------------


def bf16_params(cfg, seed: int):
    """Random weights built under jit straight to bf16, so fp32 copies of
    every layer never sit on the device at once."""
    return jax.jit(lambda k: jax.tree.map(
        lambda w: w.astype(jnp.bfloat16), T.init(k, cfg)[0]))(
            jax.random.PRNGKey(seed))


def prompts_for(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=n).tolist() for n in PROMPT_LENS]


def serve_mixed(engine, prompts, max_news) -> list:
    """All but the last request up front (more than the slots: the extra
    ones queue), the last submitted when the first request finishes, so
    it joins a running batch."""
    rids = [engine.submit(p, max_new_tokens=k)
            for p, k in zip(prompts[:-1], max_news[:-1])]
    late = None
    while engine.n_running or engine.n_queued or late is None:
        events = engine.step()
        if late is None and events["finished"]:
            late = engine.submit(prompts[-1], max_new_tokens=max_news[-1])
    out = engine.harvest()
    return [out[r] for r in rids + [late]]


def serve_phase(cfg, seed: int) -> dict:
    params = bf16_params(cfg, seed)
    scfg = ServeConfig(n_slots=SLOTS, prompt_bucket=BUCKET,
                       max_len=BUCKET + MAX_NEW, packed=True)
    engine = ServeEngine(params, cfg, SP, scfg)
    prompts = prompts_for(cfg, seed)
    t0 = time.perf_counter()
    solo = []
    for p, k in zip(prompts, MAX_NEWS):
        rid = engine.submit(p, max_new_tokens=k)
        solo.append(engine.run()[rid])
    solo_s = time.perf_counter() - t0
    engine.reset()
    t0 = time.perf_counter()
    mixed = serve_mixed(engine, prompts, MAX_NEWS)
    mixed_s = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(mixed, solo)):
        check(a == b, f"serve: request {i} stream differs from solo decode")

    b = engine.batcher
    census = pallas_call_census(b._decode, b.params, b.kv.cache, b.tokens,
                                b.positions, kernel=NM_SPMM)
    n_packed = engine.hbm_report()["n_packed"]
    check(census == n_packed > 0,
          f"packed decode: {census} nm_spmm calls for {n_packed} packed sites")

    dense = ServeEngine(params, cfg, SP, dataclasses.replace(scfg,
                                                             packed=False))
    prefill = jax.jit(partial(ST.lm_prefill_step, cfg=cfg, sp_cfg=SP))
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :len(prompts[1])] = prompts[1]
    last = jnp.asarray([len(prompts[1]) - 1])
    lp, _ = prefill(b.params, {"tokens": jnp.asarray(toks)}, last_index=last)
    ld, _ = prefill(dense.batcher.params, {"tokens": jnp.asarray(toks)},
                    last_index=last)
    err = _rel_err(lp[..., :cfg.vocab], ld[..., :cfg.vocab])
    check(err <= LOGIT_RTOL,
          f"packed vs masked-dense prefill logits: rel err {err}")
    res = {"streams_equal_solo": True, "requests": len(prompts),
           "decoded_tokens": sum(map(len, mixed)),
           "solo_wall_s": solo_s, "mixed_wall_s": mixed_s,
           "nm_spmm_calls": census, "packed_sites": n_packed,
           "prefill_logit_rel_err": err, "peak_bytes_in_use": peak_bytes(),
           "hbm": engine.hbm_report()}
    log("[serve] " + json.dumps(res, default=str))
    return res


# ---------------------------------------------------------------------------
# four chips: sharded train step and a replica fleet
# ---------------------------------------------------------------------------


def two_pod_step(cfg, opt, names):
    """One step of what compressed pod sync computes, written out without
    a pod axis: each pod's half of the batch gives a gradient; each leaf of
    it, plus that pod's error feedback, is compressed to 2 of every 8
    entries in bf16 (``ops.grad_compress``, jnp path), the rest carried
    to the next step; the optimizer steps on the mean of the two pods'
    decoded payloads.  The sync's own code does not run: no slab,
    bucket, shard_map, exchange, or own-pod decode through the
    error-feedback identity."""
    n, m = SP.n, SP.m

    def loss_fn(diff, meta, half):
        compute = ST.merge_compute(diff, meta)
        hidden, _, aux = T.forward(compute, half["tokens"], cfg, SP)
        loss = T.lm_loss(compute, hidden, half["labels"], cfg)
        return loss + ST.AUX_COEF * aux, loss

    def compress(g, e):
        if g.size % m:  # ragged leaves ride dense, as in the sync
            return g, e
        check(g.shape[-1] % m == 0, f"{g.shape}: groups cross rows")
        v, i, e = ops.grad_compress(g, e, n, m, use_pallas=False)
        return (v, i), e

    def mean(g, a, b):
        if isinstance(a, tuple):  # packed: decode both pods' payloads
            a, b = (decompress_nm(v, i, n, m, axis=-1).astype(jnp.float32)
                    for v, i in (a, b))
        return ((a + b) * 0.5).astype(g.dtype)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(state, err, b):
        diff, meta = ST.split_compute(state["compute"])
        sent, new_err, losses = [], [], []
        for p in (0, 1):
            if p:  # pod 1 starts when pod 0 is done: half the activations
                diff, sent = jax.lax.optimization_barrier((diff, sent))
            half = jax.tree.map(
                lambda x: x.reshape(2, x.shape[0] // 2, *x.shape[1:])[p], b)
            (_, loss), g = jax.value_and_grad(
                lambda d: loss_fn(d, meta, half), has_aux=True)(diff)
            g_flat, tdef = jax.tree.flatten(
                sgd.pregen_grads(ST.merge_compute(g, meta)))
            pod = [compress(gl.astype(jnp.float32), el)
                   for gl, el in zip(g_flat, tdef.flatten_up_to(err[p]))]
            sent.append([s for s, _ in pod])
            new_err.append(jax.tree.unflatten(tdef, [e for _, e in pod]))
            losses.append(loss)
        grads = [mean(g, a, b_) for g, a, b_ in zip(g_flat, *sent)]
        core, compute = sgd.update(
            ST.state_core(state), jax.tree.unflatten(tdef, grads), opt, SP,
            param_names=names, prev_compute=state["compute"], pregen=True)
        return (dict(state, **core, compute=compute), new_err,
                (losses[0] + losses[1]) / 2)

    return step


def two_pod_reference(cfg, seed: int, *, opt=OPT_SHARDED, batch=BATCH,
                      seq=SEQ, steps=STEPS) -> list:
    """``two_pod_step`` from the state and data the sharded runs start
    from, on a ``data=1,model=2`` mesh of two chips (the 4-layer step
    with two pods' residuals needs more than one chip's HBM); returns
    the losses."""
    opt = dataclasses.replace(opt, total_steps=steps)
    mesh = spmd.make_spmd_mesh("data=1,model=2", devices=jax.devices()[:2])
    bundle = ST.build_lm_train(cfg, mesh, SP, opt)
    state = jax.jit(partial(ST.init_train_state, cfg=cfg, sp_cfg=SP),
                    out_shardings=bundle.state_shardings)(
                        jax.random.PRNGKey(seed))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=bundle.state_shardings["master"])
    err = [zeros(state["master"]) for _ in (0, 1)]
    step = two_pod_step(cfg, opt, bundle.names)
    batch_sh = {k: NamedSharding(mesh, ps)
                for k, ps in bundle.input_pspecs.items()}
    losses = []
    for _, (_, b) in zip(range(steps), D.lm_stream(
            cfg.vocab, batch, seq, shardings=batch_sh, seed=seed)):
        state, err, loss = step(state, err, b)
        losses.append(float(loss))
    del state, err
    check(all(np.isfinite(losses)), f"two-pod reference: {losses}")
    log(f"[two-pod reference] {json.dumps({'losses': losses})}")
    return losses


def sharded_train_phase(cfg, seed: int, rtol=SYNC_RTOL, **kw) -> dict:
    """The 4-layer step on ``pod=2,data=1,model=2``: dense pod sync
    against the one-chip run, compressed pod sync against the two-pod
    reference."""
    kw.setdefault("opt", OPT_SHARDED)
    solo = train_run(cfg, spmd.single_device_mesh(), seed, "train 1 chip",
                     **kw)
    ref = two_pod_reference(cfg, seed, **kw)
    mesh = spmd.make_spmd_mesh("pod=2,data=1,model=2")
    dense = train_run(cfg, mesh, seed, "train pod=2 model=2 dense", **kw)
    comp = train_run(cfg, mesh, seed, "train pod=2 model=2 compress",
                     compress=True, **kw)
    check_sync(dense["losses"], solo["losses"], "dense sync vs 1 chip", rtol)
    check_sync(comp["losses"], ref, "compressed sync vs two-pod reference",
               rtol)
    return {"solo": solo["losses"], "dense_sync": dense["losses"],
            "two_pod_reference": ref, "compressed_sync": comp["losses"]}


def fleet_phase(cfg, seed: int) -> dict:
    """A ``ServeFleet`` of four one-chip replicas; every stream against
    its solo decode on replica 0."""
    params = bf16_params(cfg, seed)
    scfg = ServeConfig(n_slots=SLOTS, prompt_bucket=BUCKET,
                       max_len=BUCKET + MAX_NEW, packed=True)
    prompts = prompts_for(cfg, seed)
    fleet = ServeFleet(params, cfg, SP, scfg,
                       FleetConfig(n_replicas=4, router="least_loaded"),
                       meshes=spmd.fleet_meshes(4))
    homes = {next(iter(jax.tree.leaves(e.batcher.params)[0].devices()))
             for e in fleet.engines}
    check(len(homes) == 4, f"fleet replicas share chips: {homes}")
    solo_streams = []
    for p, k in zip(prompts, MAX_NEWS):
        rid = fleet.engines[0].submit(p, max_new_tokens=k)
        solo_streams.append(fleet.engines[0].run()[rid])
    rids = [fleet.submit(p, max_new_tokens=k)
            for p, k in zip(prompts, MAX_NEWS)]
    out = fleet.run()
    for i, r in enumerate(rids):
        check(out[r] == solo_streams[i],
              f"fleet: request {i} stream differs from solo decode")
    return {"fleet_streams_equal_solo": True, "fleet_replicas": len(homes)}


def four_chip_phase(full, seed: int) -> dict:
    """Sharded training at ``TRAIN_LAYERS``, the fleet at full depth."""
    train_cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    res = {**sharded_train_phase(train_cfg, seed), **fleet_phase(full, seed)}
    log("[4 chips] " + json.dumps(res, default=str))
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2

    full = get_arch(ARCH).full
    train_cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    log(f"{ARCH} at published widths (d_model={full.d_model}, "
        f"{full.moe.n_experts} experts top-{full.moe.top_k}, d_expert="
        f"{full.moe.d_expert}, vocab={full.vocab}), seed {args.seed}")
    log(f"cut: train depth {full.n_layers} -> {TRAIN_LAYERS} layers, "
        f"batch {BATCH} x {SEQ}, {STEPS} steps; serve at full depth "
        f"({full.n_layers} layers), {SLOTS} slots, prompt bucket "
        f"{BUCKET}, <= {MAX_NEW} new tokens")
    if args.chips == 4:
        four_chip_phase(full, args.seed)
    else:
        kernels_phase(args.seed)
        train_phase(train_cfg, make_host_mesh(), args.seed)
        serve_phase(full, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
