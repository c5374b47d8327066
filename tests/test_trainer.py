"""Fault-tolerance stack tests: checkpoint atomicity/retention/elastic
restore, straggler detection, heartbeat, auto-resume, full trainer loop."""

import json
import os
import time

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import CheckpointManager
from repro.train.fault import Heartbeat, StragglerMonitor, recover_or_init

jax.config.update("jax_platform_name", "cpu")


def _state(step=0, scale=1.0):
    return {
        "master": {"w": jnp.full((4, 8), scale, jnp.float32),
                   "b": jnp.arange(8, dtype=jnp.float32) * scale},
        "momentum": {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))},
        "step": jnp.asarray(step, jnp.int32),
    }


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        st = _state(step=7, scale=3.5)
        mgr.save(7, st, blocking=True)
        out = mgr.restore(_state())
        assert int(out["step"]) == 7
        np.testing.assert_array_equal(np.asarray(out["master"]["w"]),
                                      np.asarray(st["master"]["w"]))

    def test_async_save_commits(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, _state(3))
        mgr.wait()
        assert mgr.latest_step() == 3
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))

    def test_retention_keeps_newest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, _state(s), blocking=True)
        assert mgr.all_steps() == [3, 4]

    def test_torn_write_never_visible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        # a stale .tmp from a crashed writer must not count as a checkpoint
        os.makedirs(tmp_path / "step_00000099.tmp")
        assert mgr.latest_step() is None

    def test_structure_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, _state(), blocking=True)
        with pytest.raises(ValueError):
            mgr.restore({"only": jnp.zeros(3)})

    def test_elastic_restore_under_new_shardings(self, tmp_path):
        """Checkpoint is mesh-agnostic: restore re-device_puts under the
        current mesh's shardings (1-device container: identity mesh)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mgr = CheckpointManager(str(tmp_path))
        st = _state(5)
        mgr.save(5, st, blocking=True)
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
        out = mgr.restore(_state(), shardings=sh)
        assert out["master"]["w"].sharding == NamedSharding(mesh, P())


class TestRecoverOrInit:
    def test_fresh_when_no_checkpoint(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        st, step = recover_or_init(mgr, lambda: _state(0))
        assert step == 0 and int(st["step"]) == 0

    def test_resumes_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(11, _state(11, scale=2.0), blocking=True)
        st, step = recover_or_init(mgr, lambda: _state(0))
        assert step == 11 and float(st["master"]["w"][0, 0]) == 2.0


class TestStraggler:
    def test_flags_slow_step(self):
        mon = StragglerMonitor(threshold=2.0, warmup=2)
        for i in range(5):
            assert not mon.record(i, 0.1)
        assert mon.record(5, 0.5)   # 5x the EWMA mean
        assert not mon.record(6, 0.1)

    def test_warmup_never_flags(self):
        mon = StragglerMonitor(threshold=1.01, warmup=3)
        assert not mon.record(0, 10.0)
        assert not mon.record(1, 0.0001)

    def test_compile_step_never_seeds_mean(self):
        """Regression: step 0 carries jit compilation (here 100x a
        steady step).  Seeding the EWMA from it poisoned the mean so an
        early real straggler sailed under ``threshold x mean`` — warmup
        samples must be DISCARDED, with the mean seeded from the first
        post-warmup sample."""
        mon = StragglerMonitor(threshold=2.0, warmup=1)
        assert not mon.record(0, 10.0)     # compile-laden: discarded
        assert not mon.record(1, 0.1)      # seeds the mean
        assert mon.mean == pytest.approx(0.1)
        assert mon.record(2, 0.3)          # 3x the mean: flagged NOW
        assert mon.flagged == [(2, 0.3, pytest.approx(0.1))]
        # the straggler did not poison the mean either
        assert mon.mean == pytest.approx(0.1)

    def test_fewer_samples_than_warmup_never_seeds_the_mean(self):
        """Edge: a run killed (or a monitor queried) before ``warmup``
        samples arrive.  Every sample so far was discarded, so the EWMA
        must still be unseeded and nothing may have flagged — a mean
        accidentally seeded from a discarded warmup sample would poison
        every comparison after the restart."""
        mon = StragglerMonitor(threshold=1.01, warmup=5)
        for step, secs in enumerate((30.0, 0.001, 12.0, 0.5)):
            assert not mon.record(step, secs)   # 4 < warmup: all discarded
        assert mon.mean is None
        assert mon.flagged == []
        assert mon.count == 4
        # the first post-warmup sample seeds; the one after it compares
        assert not mon.record(4, 9.9)           # 5th: last warmup sample
        assert not mon.record(5, 0.2)           # seeds mean = 0.2
        assert mon.mean == pytest.approx(0.2)
        assert mon.record(6, 0.5)               # 2.5x: flagged


class TestHeartbeat:
    def test_beat_and_staleness(self, tmp_path):
        hb = Heartbeat(str(tmp_path / "hb.json"))
        hb.beat(3, loss=1.5)
        assert not hb.is_stale(60.0)
        data = json.load(open(tmp_path / "hb.json"))
        assert data["step"] == 3
        assert hb.age() < 5.0

    def test_two_writers_never_collide(self, tmp_path, monkeypatch):
        """Regression: during a watchdog restart the old and new process
        briefly both beat() the same path.  With a shared ``path +
        ".tmp"`` scratch name their write/replace pairs interleave — the
        loser's os.replace finds its tmp already consumed.  The barrier
        parks both writers between write and replace to force exactly
        that overlap; per-writer scratch names must survive it."""
        import threading

        from repro.train import fault as F

        path = str(tmp_path / "hb.json")
        a, b = Heartbeat(path), Heartbeat(path)
        assert a._tmp != b._tmp  # unique scratch per writer

        bar = threading.Barrier(2)
        real_dump = json.dump

        def stalling_dump(obj, f, **kw):
            real_dump(obj, f, **kw)
            bar.wait(timeout=10)  # both tmps written, neither replaced

        monkeypatch.setattr(F.json, "dump", stalling_dump)
        errors = []

        def beat(hb, step):
            try:
                hb.beat(step, loss=0.5)
            except Exception as e:  # pre-fix: FileNotFoundError here
                errors.append(e)

        threads = [threading.Thread(target=beat, args=(hb, s))
                   for hb, s in ((a, 1), (b, 2))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert errors == []
        data = json.load(open(path))  # one COMPLETE payload won
        assert data["step"] in (1, 2) and data["loss"] == 0.5

    def test_watchdog_mid_write_sees_only_committed_payloads(
            self, tmp_path, monkeypatch):
        """Edge: the watchdog fires WHILE a beat() is between write and
        replace.  The scratch file exists with a (possibly partial)
        payload, but ``path`` still holds the previous commit — age()
        must keep reading that committed payload (fresh, parseable) and
        never the in-flight scratch.  Before any commit at all, the same
        mid-write watchdog poll must report stale."""
        import threading

        from repro.train import fault as F

        path = str(tmp_path / "hb.json")
        hb = Heartbeat(path)

        in_write = threading.Event()
        release = threading.Event()
        real_dump = json.dump

        def stalling_dump(obj, f, **kw):
            real_dump(obj, f, **kw)
            in_write.set()
            assert release.wait(timeout=10)  # park before os.replace

        monkeypatch.setattr(F.json, "dump", stalling_dump)

        # -- no commit yet: watchdog during the very first write --------
        t = threading.Thread(target=hb.beat, args=(1,))
        t.start()
        assert in_write.wait(timeout=10)
        assert hb.age() is None           # nothing committed to read
        assert hb.is_stale(60.0)          # watchdog restarts: correct
        release.set()
        t.join(timeout=10)
        assert json.load(open(path))["step"] == 1

        # -- committed payload present: watchdog during the next write --
        in_write.clear()
        release.clear()
        t = threading.Thread(target=hb.beat, args=(2,), kwargs={"loss": 9.0})
        t.start()
        assert in_write.wait(timeout=10)
        age = hb.age()                    # reads the step-1 commit
        assert age is not None and age < 5.0
        assert not hb.is_stale(60.0)      # no spurious restart mid-write
        assert json.load(open(path))["step"] == 1
        release.set()
        t.join(timeout=10)
        data = json.load(open(path))      # step-2 commit landed whole
        assert data["step"] == 2 and data["loss"] == 9.0


class TestTrainerLoop:
    def test_fit_spans_once_per_step(self, tmp_path):
        """Under a profiler trace ``fit`` writes one ``fit.data``,
        ``fit.dispatch`` and ``fit.sync`` span a step."""
        import glob
        import types

        from repro.train import trainer as TR

        step = jax.jit(lambda s, b: ({"step": s["step"] + 1},
                                     {"loss": b.sum()}))
        bundle = types.SimpleNamespace(step_fn=step)
        state = {"step": jnp.zeros((), jnp.int32)}
        stream = ((t, jnp.full((4,), float(t))) for t in range(100))
        step(state, jnp.zeros((4,)))   # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            state, hist = TR.fit(bundle, state, stream,
                                 TR.TrainerConfig(total_steps=5),
                                 log_fn=lambda *_: None)
        assert [h["step"] for h in hist] == list(range(5))
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        names = [e.name for p in jax.profiler.ProfileData.from_file(path).planes
                 for line in p.lines for e in line.events]
        for span in ("fit.data", "fit.dispatch", "fit.sync"):
            assert names.count(span) == 5, span
        assert names.count("fit.checkpoint") == 0

    def test_fit_runs_checkpoints_and_history(self, tmp_path):
        from repro.configs import get_arch
        from repro.core.sparsity import SparsityConfig
        from repro.data import synthetic as D
        from repro.launch.mesh import make_host_mesh
        from repro.optim import sgd
        from repro.train import step as ST
        from repro.train import trainer as TR

        arch = get_arch("qwen3-8b")
        mesh = make_host_mesh()
        sp = SparsityConfig(n=2, m=8, method="bdwp")
        bundle = ST.build_lm_train(arch.smoke, mesh, sp,
                                   sgd.SGDConfig(total_steps=6))
        state = jax.device_put(
            ST.init_train_state(jax.random.PRNGKey(0), arch.smoke, sp_cfg=sp),
            bundle.state_shardings)
        tcfg = TR.TrainerConfig(total_steps=6, ckpt_every=3, log_every=100,
                                ckpt_dir=str(tmp_path))
        stream = D.lm_stream(arch.smoke.vocab, 2, 32)
        state, hist = TR.fit(bundle, state, stream, tcfg,
                             log_fn=lambda *_: None)
        assert len(hist) == 6
        assert all(np.isfinite(h["loss"]) for h in hist)
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.latest_step() == 6

    def test_fit_no_duplicate_save_on_aligned_final_step(self, tmp_path,
                                                         monkeypatch):
        """Regression: with total_steps % ckpt_every == 0 the loop's
        last periodic save and the post-loop "final snapshot" both
        targeted the SAME step — the blocking re-save raced the still-
        async writer on one step_XXXX.tmp.  Each step must be saved at
        most once; the final step must still be committed on disk."""
        from repro.configs import get_arch
        from repro.core.sparsity import SparsityConfig
        from repro.data import synthetic as D
        from repro.launch.mesh import make_host_mesh
        from repro.optim import sgd
        from repro.train import step as ST
        from repro.train import trainer as TR

        arch = get_arch("qwen3-8b")
        mesh = make_host_mesh()
        sp = SparsityConfig(n=2, m=8, method="bdwp")
        bundle = ST.build_lm_train(arch.smoke, mesh, sp,
                                   sgd.SGDConfig(total_steps=4))
        state = jax.device_put(
            ST.init_train_state(jax.random.PRNGKey(0), arch.smoke, sp_cfg=sp),
            bundle.state_shardings)

        calls = []
        orig_save = CheckpointManager.save

        def spy(self, step, st, blocking=False):
            calls.append(step)
            return orig_save(self, step, st, blocking=blocking)

        monkeypatch.setattr(CheckpointManager, "save", spy)
        tcfg = TR.TrainerConfig(total_steps=4, ckpt_every=2, log_every=100,
                                ckpt_dir=str(tmp_path))
        TR.fit(bundle, state, D.lm_stream(arch.smoke.vocab, 2, 32), tcfg,
               log_fn=lambda *_: None)
        # pre-fix: [2, 4, 4] — step 4 written twice, async + blocking
        assert calls == [2, 4]
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.latest_step() == 4  # the async save still committed
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))

    def test_fit_resume_keys_off_state_step(self, tmp_path):
        """Auto-resume bookkeeping: after a restart the data iterator
        begins at 0 while the restored state step does not.  Checkpoint
        keys must come from state["step"] (the old iterator-keyed saves
        collided/regressed and misfired the save guard), the stale
        iterator must fast-forward, and every saved checkpoint's
        directory key must equal its internal step."""
        from repro.configs import get_arch
        from repro.core.sparsity import SparsityConfig
        from repro.data import synthetic as D
        from repro.launch.mesh import make_host_mesh
        from repro.optim import sgd
        from repro.train import step as ST
        from repro.train import trainer as TR

        arch = get_arch("qwen3-8b")
        mesh = make_host_mesh()
        sp = SparsityConfig(n=2, m=8, method="bdwp")
        bundle = ST.build_lm_train(arch.smoke, mesh, sp,
                                   sgd.SGDConfig(total_steps=8))
        state = jax.device_put(
            ST.init_train_state(jax.random.PRNGKey(0), arch.smoke, sp_cfg=sp),
            bundle.state_shardings)
        mgr = CheckpointManager(str(tmp_path), keep=0)

        tcfg = TR.TrainerConfig(total_steps=4, ckpt_every=2, log_every=100,
                                ckpt_dir=str(tmp_path))
        state, hist1 = TR.fit(bundle, state, D.lm_stream(arch.smoke.vocab, 2, 32),
                              tcfg, log_fn=lambda *_: None)
        assert [h["step"] for h in hist1] == [0, 1, 2, 3]
        assert mgr.all_steps() == [2, 4]

        # crash + restart: restore newest, hand fit a FRESH iterator (0-based)
        restored = mgr.restore(jax.tree.map(jnp.zeros_like, state),
                               shardings=bundle.state_shardings)
        assert int(restored["step"]) == 4
        tcfg2 = TR.TrainerConfig(total_steps=8, ckpt_every=2, log_every=100,
                                 ckpt_dir=str(tmp_path))
        state2, hist2 = TR.fit(bundle, restored,
                               D.lm_stream(arch.smoke.vocab, 2, 32),
                               tcfg2, log_fn=lambda *_: None)
        # resumed history continues at the optimizer step, no regression
        assert [h["step"] for h in hist2] == [4, 5, 6, 7]
        assert mgr.all_steps() == [4, 6, 8]  # keep=3 retention pruned 2
        # every checkpoint's directory key equals its internal step
        like = jax.tree.map(jnp.zeros_like, state)
        for s in mgr.all_steps():
            ck = mgr.restore(like, step=s, shardings=bundle.state_shardings)
            assert int(ck["step"]) == s
        # fast-forward consumed the stream at the right offset: a run fed
        # a correctly-offset stream lands on the identical final state
        restored_b = mgr.restore(jax.tree.map(jnp.zeros_like, state),
                                 step=4, shardings=bundle.state_shardings)
        state3, _ = TR.fit(bundle, restored_b,
                           D.lm_stream(arch.smoke.vocab, 2, 32, start=4),
                           tcfg2, log_fn=lambda *_: None)
        for a, b in zip(jax.tree.leaves(state2["master"]),
                        jax.tree.leaves(state3["master"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCompileCache:
    """The persistent compile cache sits where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path in the checkout; the cache key includes
    the directory, so it must never move between runs."""

    @pytest.fixture
    def cache_dir(self):
        old = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", old)

    def test_env_variable_left_to_jax(self, cache_dir, monkeypatch, tmp_path):
        from repro.launch.compile_cache import setup_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    @pytest.mark.parametrize("env", [None, ""], ids=["unset", "empty"])
    def test_fixed_checkout_path(self, cache_dir, monkeypatch, env):
        from repro.launch.compile_cache import CHECKOUT, setup_compile_cache

        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        first = setup_compile_cache()
        assert first == str(CHECKOUT / ".jax_cache") == setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert (CHECKOUT / "chip_smoke.py").is_file()
