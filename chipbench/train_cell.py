"""Training cell: the program's BDWP trainer (``build_lm_train`` driven by
``trainer.fit``), timed over the window.

Set-up builds one step bundle and its state from the seed, compiles the
step for the cell's batch, and drives the first ``checked_steps`` steps
through ``fit`` with the window's own feed.  Before the window moves the
state on, it reads, per leaf of the master tree, the norm of the first
gradient (from the momentum after step 1, less the weight decay and
SR-STE terms the optimizer added) and the norm of the weights' change
after the checked steps.  The window continues the same object.  After
it, the reference follows the checked steps from the same seed and the
two are compared.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding

from chipbench import spec
from chipbench import traffic as TF


def leaf_names(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class Feed:
    """The benchmark's batches, placed under the step's input shardings."""

    def __init__(self, mix: dict, vocab: int, seed: int, shardings: dict):
        self.mix, self.vocab, self.seed, self.sh = mix, vocab, seed, shardings

    def host(self, step: int):
        return TF.train_batch(self.mix, self.vocab, self.seed, step)

    def batch(self, step: int) -> dict:
        with jax.profiler.TraceAnnotation("chipbench.feed"):
            tokens, labels = self.host(step)
            return {"tokens": jax.device_put(tokens, self.sh["tokens"]),
                    "labels": jax.device_put(labels, self.sh["labels"])}

    def take(self, start: int, n: int):
        for t in range(start, start + n):
            yield t, self.batch(t)

    def until(self, start: int, deadline: float):
        t = start
        while time.perf_counter() < deadline:
            yield t, self.batch(t)
            t += 1


class TimedStep:
    """The compiled step, recording how long each call takes to return:
    its dispatch on the host, the rest of a ``fit`` step being the wait
    for the device."""

    def __init__(self, fn):
        self.fn, self.dispatch = fn, []

    def __call__(self, state, batch):
        t0 = time.perf_counter()
        out = self.fn(state, batch)
        self.dispatch.append(time.perf_counter() - t0)
        return out


def _first_grad_norms(cfg, sp, opt, names):
    """jit: (key, momentum after one step) -> per-leaf |g0|, undoing the
    decay terms the optimizer added to the gradient it was given."""
    from repro.core import bdwp
    from repro.core.sparsity import nm_mask
    from repro.models import transformer_lm as T
    from repro.optim import sgd

    def fn(key, mom):
        w0 = jax.tree_util.tree_leaves(T.init(key, cfg)[0])
        out = []
        for name, w, v in zip(names, w0, jax.tree_util.tree_leaves(mom)):
            w = w.astype(jnp.float32)
            g = v - opt.weight_decay * w
            lshape, off = sgd._logical_shape(name, w.shape)
            if bdwp.decays(name, lshape, sp):
                mask = nm_mask(w, sp.n, sp.m, axis=bdwp.ff_group_axis(lshape) + off)
                g = g - sp.lam * jnp.where(mask, 0.0, w)
            out.append(jnp.linalg.norm(g.ravel()))
        return out

    return jax.jit(fn)


def _change_norms(cfg):
    from repro.models import transformer_lm as T

    def fn(key, master):
        w0 = jax.tree_util.tree_leaves(T.init(key, cfg)[0])
        return [jnp.linalg.norm((w - a.astype(jnp.float32)).ravel())
                for w, a in zip(jax.tree_util.tree_leaves(master), w0)]

    return jax.jit(fn)


def build(conf: dict, mix: dict, log, devices) -> dict:
    """The program's step bundle, compiled for the mix's batch on a
    data-parallel mesh of ``devices`` (the cell's chips), and what makes
    its state and its feed from a seed."""
    from repro.core.sparsity import SparsityConfig
    from repro.optim import sgd
    from repro.train import step as ST
    from repro.train import trainer as TR

    cfg = spec.family(conf).program_config(conf)
    sp = SparsityConfig(**mix["sparsity"])
    o = mix["optimizer"]
    opt = sgd.SGDConfig(lr=o["lr"], momentum=o["momentum"],
                        weight_decay=o["weight_decay"],
                        warmup_steps=o["warmup_steps"], total_steps=1 << 30,
                        min_lr_frac=o["min_lr_frac"])
    path = mix["path"]
    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    bundle = ST.build_lm_train(cfg, mesh, sp, opt, **path)
    init = jax.jit(partial(ST.init_train_state, cfg=cfg, sp_cfg=sp,
                           pregen=path["pregen"],
                           pregen_pack=path["pregen_pack"]),
                   out_shardings=bundle.state_shardings)
    sh = {k: NamedSharding(mesh, ps) for k, ps in bundle.input_pspecs.items()}
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                           sharding=s),
                         state, bundle.state_shardings)
    tok = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32,
                               sharding=sh["tokens"])
    t0 = time.perf_counter()
    compiled = bundle.step_fn.lower(state, {"tokens": tok, "labels": tok}).compile()
    log(f"step compiled in {time.perf_counter() - t0:.1f} s")
    return {"cfg": cfg, "sp": sp, "opt": opt, "init": init, "shardings": sh,
            "bundle": dataclasses.replace(bundle, step_fn=compiled),
            "names": leaf_names(state["master"]), "fit": TR.fit,
            "tcfg": TR.TrainerConfig(total_steps=1 << 30, log_every=1 << 30),
            "grad_fn": _first_grad_norms(cfg, sp, opt, leaf_names(state["master"])),
            "change_fn": _change_norms(cfg), "conf": conf, "mix": mix}


def first_steps(b: dict, seed: int, log) -> dict:
    """State from the seed, driven through the checked steps by ``fit``
    with the window's feed; returns the run's state and its readings."""
    quiet = lambda s: None  # noqa: E731
    key = jax.random.PRNGKey(TF.key_seed(seed))
    state = b["init"](key)
    feed = Feed(b["mix"], b["cfg"].vocab, seed, b["shardings"])
    n_check = b["mix"]["checked_steps"]
    state, hist = b["fit"](b["bundle"], state, feed.take(0, 1), b["tcfg"],
                           log_fn=quiet)
    grad = b["grad_fn"](key, state["momentum"])
    state, more = b["fit"](b["bundle"], state, feed.take(1, n_check - 1),
                           b["tcfg"], log_fn=quiet)
    change = b["change_fn"](key, state["master"])
    first = {"loss": [h["loss"] for h in hist + more],
             "grad": dict(zip(b["names"], map(float, grad))),
             "change": dict(zip(b["names"], map(float, change)))}
    log(f"first {n_check} losses {first['loss']}")
    return {**b, "state": state, "feed": feed, "first": first, "seed": seed,
            "n_check": n_check}


def setup(conf: dict, mix: dict, seed: int, log, devices) -> dict:
    return first_steps(build(conf, mix, log, devices), seed, log)


def window(st: dict, seconds: float, log) -> dict:
    mix = st["mix"]
    timed = TimedStep(st["bundle"].step_fn)
    bundle = dataclasses.replace(st["bundle"], step_fn=timed)
    t0 = time.perf_counter()
    state, hist = st["fit"](bundle, st["state"],
                            st["feed"].until(st["n_check"], t0 + seconds),
                            st["tcfg"], log_fn=lambda s: None)
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    st["state"] = state
    steps = len(hist)
    tokens = steps * mix["batch"] * mix["seq"]
    bad = sum(not np.isfinite(h["loss"]) for h in hist)
    secs = [h["sec"] for h in hist]
    med = statistics.median(secs)
    log(f"window: {steps} steps of {mix['batch']} x {mix['seq']} tokens in "
        f"{t1 - t0:.3f} s; step seconds median {med:.4f}, "
        f"min {min(secs):.4f}, max {max(secs):.4f}; last loss {hist[-1]['loss']:.4f}")
    slow = [(i, round(x, 4), round(d, 4))
            for i, (x, d) in enumerate(zip(secs, timed.dispatch)) if x > 1.5 * med]
    log(f"window: step dispatch median {statistics.median(timed.dispatch):.5f} s, "
        f"max {max(timed.dispatch):.5f} s; steps over 1.5 x the median "
        f"(index in the window, s, of which dispatch): {slow}")
    sp = mix["sparsity"]
    per_tok = spec.family(st["conf"]).train_flops_per_token(
        st["conf"], mix["seq"], sp["n"], sp["m"])
    log(f"work per step: {per_tok['sparse'] * mix['batch'] * mix['seq']:.4e} "
        f"FLOP as BDWP needs it, {per_tok['dense'] * mix['batch'] * mix['seq']:.4e} "
        f"dense-equivalent")
    return {"attempted": steps, "failed": bad, "window_s": t1 - t0,
            "steps": steps, "tokens": tokens, "flops_per_token": per_tok,
            "metrics": {"train_tokens_per_s": tokens / (t1 - t0)}}


def reference_readings(conf: dict, mix: dict, seed: int, control: bool = False,
                       drop_half: bool = False) -> dict:
    sp, o = mix["sparsity"], mix["optimizer"]
    batches = [TF.train_batch(mix, conf["vocab_size"], seed, t)
               for t in range(mix["checked_steps"])]
    return spec.family(conf).train_readings(
        conf, jax.random.PRNGKey(TF.key_seed(seed)), batches,
        n=sp["n"], m=sp["m"], lr=o["lr"], momentum=o["momentum"],
        weight_decay=o["weight_decay"], lam=sp["lam"], control=control,
        drop_half=drop_half)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: each step's loss as a share of the
    reference's, and by the worst leaf the first gradient's norm and the
    weights' change, each as a share of the reference leaf's norm or of
    the median leaf's, whichever is larger.  The change leaves out leaves
    whose reference gradient is under a thousandth of the median leaf's."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    out = {}
    for t, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss_step{t}"] = abs(a - b) / abs(b)

    def worst(pa: dict, rb: dict, names) -> tuple:
        med = statistics.median(rb[k] for k in names)
        gaps = {k: abs(pa[k] - rb[k]) / max(rb[k], med) for k in names}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    gmed = statistics.median(ref["grad"].values())
    moving = [k for k, g in ref["grad"].items() if g >= 1e-3 * gmed]
    out["grad"], gk = worst(prog["grad"], ref["grad"], list(ref["grad"]))
    out["change"], ck = worst(prog["change"], ref["change"], moving)
    out["_worst"] = {"grad": gk, "change": ck,
                     "still": sorted(set(ref["grad"]) - set(moving))}
    return out


def check(st: dict, res: dict, limits: dict, log) -> dict:
    first, conf, mix, seed = st["first"], st["conf"], st["mix"], st["seed"]
    st.clear()
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    ref = reference_readings(conf, mix, seed)
    got = compare(first, ref)
    worst = got.pop("_worst")
    log(f"reference: losses {ref['loss']}, program {first['loss']}; worst "
        f"leaves: gradient {worst['grad']}, change {worst['change']}; left "
        f"out of the change: {worst['still']}; {time.perf_counter() - t0:.1f} s")
    for k in sorted(set(got) - set(limits)):
        log(f"read, not compared: {k} {got[k]!r}")
    return {k: {"value": got[k], "limit": lim} for k, lim in limits.items()}
