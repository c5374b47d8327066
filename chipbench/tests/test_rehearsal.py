"""CPU rehearsals of each cell at smoke size through the
harness's own ``run``: the chip is not looked for (the test hands ``run``
the CPU device), and the limits are the smoke-size ones below.  Then
the same runs with the timed path broken underneath, and with the
reference in float8 in the program's place: ``correct`` must read false.
A family that is one new file runs a cell the same way, and the
reference's readings stay those recorded at smoke size.
"""

import argparse
import json
import sys
from pathlib import Path

import jax
import pytest

import smoke
from chipbench import run as R
from chipbench import spec

# about ten times what sound smoke-size runs read on the CPU
TRAIN_LIMITS = {"loss_step0": 2e-3, "loss_step1": 5e-3, "loss_step2": 3e-2,
                "grad": 5e-2, "change": 5e-2}
SEED = 2 ** 31 + 12345


def run_cell(monkeypatch, name, conf, mix, limits, trace=0, seconds=3.0):
    bench = spec.benchmark()
    cell = dict(spec.find(bench["workloads"], name, "workload"))
    from repro.launch import compile_cache

    # no persistent cache for CPU programs in the checkout
    monkeypatch.setattr(compile_cache, "setup_compile_cache", lambda: None)
    monkeypatch.setattr(spec, "limits", lambda workload, root=None: limits)
    monkeypatch.setattr(spec, "peaks", lambda kind, root=None: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    args = argparse.Namespace(workload=name, seed=SEED, seconds=seconds,
                              trace=trace)
    return R.run(args, bench, cell, conf, mix, jax.devices()[:cell["chips"]])


def assert_result(out, names):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(names)
    assert out["device"]["platform"] == "cpu"


TRAIN_CELLS = [("granite-train-bdwp28", smoke.GRANITE),
               ("qwen3-8b-train-bdwp28", smoke.QWEN)]


@pytest.mark.parametrize("name,conf", TRAIN_CELLS, ids=["granite", "qwen"])
def test_train_cell(monkeypatch, name, conf):
    out = run_cell(monkeypatch, name, conf, smoke.TRAIN, TRAIN_LIMITS)
    assert out["correct"], out["checks"]
    assert_result(out, ["train_tokens_per_s", "setup_s"])
    assert set(out["checks"]) == set(TRAIN_LIMITS)


def test_train_cell_traced(monkeypatch):
    out = run_cell(monkeypatch, "granite-train-bdwp28", smoke.GRANITE,
                   smoke.TRAIN, TRAIN_LIMITS, trace=1)
    assert out["correct"], out["checks"]
    # the CPU trace has no device plane and the CPU no allocator peak:
    # every reader finds nothing and says so by its absence
    assert out["metrics"] == {}
    assert out["device"]["busy_s"] == 0.0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_fault_state_unchanged(monkeypatch):
    from repro.optim import sgd

    update = sgd.update

    def frozen(state, grads, opt_cfg, sp_cfg, param_names=None, *,
               prev_compute=None, **kw):
        new, _ = update(state, grads, opt_cfg, sp_cfg, param_names,
                        prev_compute=prev_compute, **kw)
        return dict(state, step=new["step"]), prev_compute

    monkeypatch.setattr(sgd, "update", frozen)
    out = run_cell(monkeypatch, "granite-train-bdwp28", smoke.GRANITE,
                   smoke.TRAIN, TRAIN_LIMITS)
    assert not out["correct"]
    assert out["checks"]["change"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_fault_half_batch(monkeypatch):
    from repro.train import step as ST

    step = ST.lm_train_step

    def half(state, batch, **kw):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, **kw)

    monkeypatch.setattr(ST, "lm_train_step", half)
    out = run_cell(monkeypatch, "granite-train-bdwp28", smoke.GRANITE,
                   smoke.TRAIN, TRAIN_LIMITS)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("conf", [smoke.GRANITE, smoke.QWEN], ids=["granite", "qwen"])
def test_control_train(conf):
    from chipbench import train_cell as TC

    ref = TC.reference_readings(conf, smoke.TRAIN, SEED)
    low = TC.reference_readings(conf, smoke.TRAIN, SEED, control=True)
    got = TC.compare(low, ref)
    got.pop("_worst")
    assert any(got[k] > TRAIN_LIMITS[k] for k in TRAIN_LIMITS), got


@pytest.mark.parametrize("conf", [smoke.GRANITE, smoke.QWEN], ids=["granite", "qwen"])
def test_reference_readings_stay(conf):
    """Losses and per-leaf gradient and change norms of the reference,
    equal to the last digit to those recorded before the reference moved
    into its family (``testdata/smoke_readings.json``)."""
    from chipbench import train_cell as TC

    recorded = json.loads((spec.ROOT / "testdata" / "smoke_readings.json").read_text())
    assert TC.reference_readings(conf, smoke.TRAIN, SEED) == recorded[conf["name"]]


def test_a_family_is_found_by_its_file_name(monkeypatch):
    """A configuration naming ``wrapped_lm`` runs a cell through the
    harness with ``correct`` true: the harness finds the family by its
    file's name alone, and reaches the program mapping, the counts and
    the reference only through it."""
    from chipbench import families

    name = "chipbench.families.wrapped_lm"
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(Path(__file__).parent / "families")])
    try:
        out = run_cell(monkeypatch, "granite-train-bdwp28",
                       dict(smoke.GRANITE, family="wrapped_lm"), smoke.TRAIN,
                       TRAIN_LIMITS)
        calls = sys.modules[name].CALLS
    finally:
        sys.modules.pop(name, None)
    assert out["correct"], out["checks"]
    assert calls == {"program_config", "train_readings", "train_flops_per_token"}
