"""Per-layer device time and the host's sync lag (``layer_time.py``) on
a synthetic trace whose answers are counted by hand.

One device.  Window (0, 10).  The step program ``lm_train_step`` runs at
1-3 s (A) and 4-6 s (B), and again at 9.5-10.5 s, past the window's end;
another program runs at 6.5-7 s.  A: a loop 1-2.5 holding an ff fusion
(0.6 s) and a bp fusion (0.4 s), then an unscoped copy (0.3 s).  B: the
loop 4-5 holding the ff fusion (0.4 s), an instruction the table lacks
(0.2 s) and a mixed update fusion (0.6 s).
"""

import pytest

from chipbench import layer_time as LT
from chipbench import spec
from chipbench.trace import Event, Trace

DEV = "/device:TPU:0"
STEP, OTHER = "jit_lm_train_step(11)", "jit_other(12)"


def op(name, s, t):
    return Event(f"%{name} = bf16[8]{{0}} fusion()", s, t)


def trace(with_missing=True):
    ops = [op("while.1", 1.0, 2.5), op("fusion.1", 1.2, 1.8),
           op("fusion.2", 1.8, 2.2), op("copy.3", 2.6, 2.9),
           op("while.1", 4.0, 5.0), op("fusion.1", 4.1, 4.5),
           op("fusion.4", 5.3, 5.9),
           op("fusion.1", 6.6, 6.9),      # the other program
           op("fusion.1", 9.6, 9.9)]      # the run past the window
    if with_missing:
        ops.append(op("fusion.9", 5.0, 5.2))
    ops.sort(key=lambda e: (e.start, -e.end))
    modules = [Event(STEP, 1.0, 3.0), Event(STEP, 4.0, 6.0),
               Event(OTHER, 6.5, 7.0), Event(STEP, 9.5, 10.5)]
    host = [Event("fit.sync", 0.9, 3.2), Event("fit.sync", 3.9, 7.5),
            Event("fit.sync", 10.2, 10.8), Event("fit.data", 3.2, 3.3)]
    return Trace({DEV: ops}, {DEV: modules}, host)


TABLE = {"while.1": ("blocks", False), "fusion.1": ("ff", False),
         "fusion.2": ("bp", False), "copy.3": ("unscoped", False),
         "fusion.4": ("update", True)}
WIN = (0.0, 10.0)


def test_split_counted_by_hand():
    sp = LT.split(trace(), WIN, TABLE)
    assert sp["runs"] == 2
    assert sp["program_s"] == pytest.approx(4.0)
    assert sp["layers"] == pytest.approx({"blocks": 0.5 + 0.6, "ff": 0.6 + 0.4,
                                          "bp": 0.4, "unscoped": 0.3,
                                          "update": 0.6})
    assert sp["missing"] == pytest.approx({"fusion.9": 0.2})
    assert sp["mixed_s"] == pytest.approx(0.6)
    assert [n for n, _ in sp["top"]["blocks"]] == ["while.1"]


def test_sync_lags_counted_by_hand():
    lags = LT.sync_lags(trace(), WIN)
    assert [s for s, _ in lags] == [0.9, 3.9]        # the third ends late
    assert [lag for _, lag in lags] == pytest.approx([3.2 - 3.0, 7.5 - 6.0])


def ctx_of(tr, logs):
    return {"trace": tr, "window": WIN, "log": logs.append, "conf": None,
            "mix": None}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(LT, "_step_text", lambda ctx, n: "")
    monkeypatch.setattr(LT, "layer_table", lambda text: TABLE)


def test_readers(table):
    logs = []
    ctx = ctx_of(trace(with_missing=False), logs)
    read = {m: spec.metric_reader(m)(ctx) for m in
            ("train.ff_ms", "train.bp_ms", "train.wu_ms", "train.blocks_ms",
             "train.update_ms", "moe.dispatch_ms", "train.unscoped_pct",
             "train.mixed_fusion_pct", "train.sync_lag_ms")}
    assert read == pytest.approx({
        "train.ff_ms": 500.0, "train.bp_ms": 200.0, "train.wu_ms": 0.0,
        "train.blocks_ms": 550.0, "train.update_ms": 300.0,
        "moe.dispatch_ms": 0.0, "train.unscoped_pct": 100 * 0.3 / 3.4,
        "train.mixed_fusion_pct": 100 * 0.6 / 3.4,
        "train.sync_lag_ms": 1e3 * (0.2 + 1.5) / 2})
    assert any("max 1500.000 ms" in line for line in logs)
    assert any("span two layers hold 17.65%" in line for line in logs)


def test_missing_instructions_over_one_percent_read_nothing(table):
    ctx = ctx_of(trace(), [])
    assert spec.metric_reader("train.ff_ms")(ctx) is None
    assert spec.metric_reader("train.unscoped_pct")(ctx) is None
    assert spec.metric_reader("train.mixed_fusion_pct")(ctx) is None
    assert spec.metric_reader("train.sync_lag_ms")(ctx) is not None


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    monkeypatch.setattr(LT, "_step_text", lambda ctx, n: "")
    monkeypatch.setattr(LT, "layer_table", lambda text: {
        name: ("unscoped", False) for name in TABLE})
    tr = trace(with_missing=False)
    tr.host = [e for e in tr.host if e.name != "fit.sync"]
    ctx = ctx_of(tr, [])
    for m in ("train.ff_ms", "moe.dispatch_ms", "train.unscoped_pct",
              "train.mixed_fusion_pct", "train.sync_lag_ms"):
        assert spec.metric_reader(m)(ctx) is None


def test_no_step_run_reads_nothing():
    tr = trace()
    tr.modules[DEV] = [e for e in tr.modules[DEV] if e.name == OTHER]
    assert LT.layer_ms(ctx_of(tr, [])) is None


REBUILD = r'''
import re, sys, types
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from chipbench import layer_time as LT, train_cell

x = jnp.ones((128,))

def step(scope):
    def lm_train_step(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2 + jnp.cos(x)
    return jax.jit(lm_train_step).lower(x).compile()

def scopes(text):
    return set(re.findall(r'op_name="[^"/]*/(\w+)/', text))

assert scopes(step("ff").as_text()) == {"ff"}
assert scopes(step("bp").as_text()) == {"ff"}    # the cache's own key
train_cell.build = lambda conf, mix, log, devices: {
    "bundle": types.SimpleNamespace(step_fn=step("bp"))}
text = LT._step_text({"conf": None, "mix": {"kind": "train"}, "log": print}, 1)
assert scopes(text) == {"bp"}, scopes(text)
'''


def test_the_rebuild_reads_its_own_scope_names(tmp_path):
    """A step cached from the same computation under other scope names
    brings those names back from the compile cache; the rebuild does
    not take them."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(LT.__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(repo / "src"), str(repo)]))
    subprocess.run([sys.executable, "-c", REBUILD, str(tmp_path)], env=env,
                   cwd=repo, check=True, timeout=300)


DECLARED = r'''
import sys
from chipbench import layer_time as LT

assert LT.LAYERS == LT.BASE + ("mla_latent",), LT.LAYERS
assert LT.layer_of("jit(f)/transpose(jvp(blocks))/mla_latent/dot_general") == "mla_latent"
table = LT.layer_table(sys.argv[1])
assert table["d"] == ("mla_latent", False), table
'''

TABLE_HLO = """HloModule jit_t

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %d = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %x), metadata={op_name="jit(t)/blocks/mla_latent/add"}
}
"""


def test_a_scope_declared_by_a_reader_file_is_a_layer(tmp_path):
    """A reader added as a new file that declares a scope of its own makes
    that scope a layer of the table, with no edit to ``layer_time.py``."""
    import os
    import shutil
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(LT.__file__).resolve().parents[1]
    shutil.copytree(repo / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "chipbench" / "metrics" / "train.mla_latent_ms.py").write_text(
        'from chipbench import layer_time as LT\n\nSCOPES = ("mla_latent",)\n\n\n'
        'def read(ctx):\n    return LT.read_layer(ctx, "mla_latent")\n')
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), str(repo / "src")]))
    subprocess.run([sys.executable, "-c", DECLARED, TABLE_HLO], env=env,
                   cwd=tmp_path, check=True, timeout=300)
    assert "mla_latent" not in LT.LAYERS
