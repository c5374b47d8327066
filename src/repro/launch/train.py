"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train \\
      --arch qwen3-8b --smoke --steps 50 --method bdwp --nm 2:8 \\
      --ckpt-dir /tmp/run1 [--resume] [--watchdog]

Drives the full stack: config -> mesh -> StepBundle (resolved shardings)
-> synthetic data stream -> trainer loop (checkpoints, heartbeat,
straggler monitor).  ``--smoke`` selects the reduced config (CPU-sized);
the full configs are exercised via the dry-run (launch/dryrun.py).

``--watchdog`` wraps the run in a supervisor: if the heartbeat file goes
stale (crash / hang / SIGKILL'd host), the training process is restarted
and auto-resumes from the newest checkpoint — the single-host analogue
of the cluster controller's evict-and-restart path.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import jax
import numpy as np


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (default on CPU containers)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--method", default="bdwp",
                    choices=["dense", "srste", "sdgp", "sdwp", "bdwp"])
    ap.add_argument("--nm", default="2:8")
    ap.add_argument("--granularity", default="element",
                    choices=["element", "shared"])
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="N:M cross-pod gradient compression (needs a "
                         "mesh with a 'pod' axis, e.g. --mesh "
                         "pod,data,model)")
    ap.add_argument("--grad-estimator", default="topk",
                    choices=["topk", "mvue"],
                    help="gradient sparsifier for --compress: topk with "
                         "error feedback, or the unbiased MVUE sampler "
                         "(arXiv 2203.10991)")
    ap.add_argument("--bucket-elems", type=int, default=1 << 20,
                    help="compressed-sync bucket size in elements "
                         "(must be a multiple of M)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--mesh", default=None,
                    help="mesh spec over the visible devices, e.g. "
                         "'pod,data,model' (auto-factored) or "
                         "'pod=2,data=2,model=2'; with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 this runs real SPMD on a CPU host. "
                         "Default: host mesh (data x model-parallel)")
    ap.add_argument("--watchdog", action="store_true")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run_training(args) -> int:
    from jax.sharding import NamedSharding

    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.data import synthetic as D
    from repro.launch.mesh import make_host_mesh
    from repro.optim import sgd
    from repro.train import step as ST
    from repro.train import trainer as TR
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault import recover_or_init

    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.full
    n, m = (int(v) for v in args.nm.split(":"))
    sp_cfg = SparsityConfig(n=n, m=m, method=args.method,
                            granularity=args.granularity)
    opt_cfg = sgd.SGDConfig(lr=args.lr, total_steps=args.steps)
    if args.mesh:
        from repro.launch.spmd import make_spmd_mesh
        if args.model_parallel != 1:
            print("[warn] --model-parallel ignored: --mesh controls the "
                  "axis sizes (use e.g. --mesh pod,data,model="
                  f"{args.model_parallel})")
        mesh = make_spmd_mesh(args.mesh)
    else:
        mesh = make_host_mesh(model=args.model_parallel)
    # compression is the cross-pod hop; without a pod axis the state
    # must not carry an error-feedback buffer the bundle doesn't shard
    compress = args.compress and "pod" in mesh.axis_names
    if args.compress and not compress:
        print("[warn] --compress ignored: mesh has no 'pod' axis "
              "(use --mesh pod,data,model)")
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} | "
          f"{args.arch} ({'smoke' if args.smoke else 'full'}) | "
          f"{args.method} {n}:{m} {args.granularity}"
          + (" | compressed pod sync" if compress else ""))

    if arch.family == "encdec":
        bundle = ST.build_encdec_train(cfg, mesh, sp_cfg, opt_cfg)
    else:
        from repro.optim.compress import GradCompressConfig
        grad_sync = GradCompressConfig(
            n=n, m=m, estimator=args.grad_estimator,
            bucket_elems=args.bucket_elems) if compress else None
        bundle = ST.build_lm_train(cfg, mesh, sp_cfg, opt_cfg,
                                   compress=compress, grad_sync=grad_sync)

    def fresh():
        key = jax.random.PRNGKey(args.seed)
        state = ST.init_train_state(key, cfg, family=arch.family,
                                    compress=compress, sp_cfg=sp_cfg,
                                    mesh=mesh)
        return jax.device_put(state, bundle.state_shardings)

    if args.resume and args.ckpt_dir:
        from functools import partial

        mgr = CheckpointManager(args.ckpt_dir)
        # restore_with_pregen upgrades pre-pregen checkpoints (no
        # "compute" leaf) by regenerating the operands from master
        state, _ = recover_or_init(
            mgr, fresh, shardings=bundle.state_shardings,
            restore_fn=partial(ST.restore_with_pregen, mgr, sp_cfg=sp_cfg))
    else:
        state = fresh()

    batch_sh = {k: NamedSharding(mesh, ps)
                for k, ps in bundle.input_pspecs.items()}
    if arch.family == "encdec":
        stream = D.encdec_stream(cfg.vocab, args.batch, args.seq,
                                 cfg.d_model, shardings=batch_sh,
                                 seed=args.seed, start=int(state["step"]))
    else:
        prefix = 8 if arch.prefix_len else 0
        stream = D.lm_stream(cfg.vocab, args.batch, args.seq,
                             shardings=batch_sh, seed=args.seed,
                             start=int(state["step"]), prefix=prefix,
                             d_model=cfg.d_model)

    tcfg = TR.TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        log_every=args.log_every, ckpt_dir=args.ckpt_dir,
        heartbeat_path=(os.path.join(args.ckpt_dir, "heartbeat.json")
                        if args.ckpt_dir else None))
    state, history = TR.fit(bundle, state, stream, tcfg)
    final = history[-1]["loss"] if history else float("nan")
    print(f"done: {len(history)} steps, final loss {final:.4f}")
    return 0


def run_watchdog(args, argv) -> int:
    """Supervise: restart-on-stale-heartbeat until steps complete.

    The supervisor never touches a JAX backend (no device query, no
    array, no compile — importing jax initializes nothing): a chip
    belongs to one process, and the child it starts must be able to
    claim it."""
    assert args.ckpt_dir, "--watchdog requires --ckpt-dir"
    hb_path = os.path.join(args.ckpt_dir, "heartbeat.json")
    child_argv = [a for a in argv if a != "--watchdog"] + ["--resume"]
    attempts = 0
    while attempts < 10:
        attempts += 1
        proc = subprocess.Popen([sys.executable, "-m", "repro.launch.train",
                                 *child_argv],
                                env=dict(os.environ))
        while proc.poll() is None:
            time.sleep(2.0)
            try:
                age = time.time() - os.path.getmtime(hb_path)
            except OSError:
                continue
            if age > args.heartbeat_timeout:
                print(f"[watchdog] heartbeat stale ({age:.0f}s) — "
                      f"restarting from latest checkpoint")
                proc.kill()
                proc.wait()
                break
        if proc.returncode == 0:
            return 0
    return 1


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    if args.watchdog:
        sys.exit(run_watchdog(args, argv))
    sys.exit(run_training(args))


if __name__ == "__main__":
    main()
