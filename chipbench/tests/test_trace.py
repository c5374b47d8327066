"""The trace reduction on a trace recorded on one TPU v5e chip: an
``nm_spmm`` call (program ``_lambda``) and a small ``mlp`` program, three
times each, with a 2 ms host sleep (``host_gap``) between them.  The
expected numbers are counted by hand from the events' nanoseconds."""

from pathlib import Path

import pytest

from chipbench import trace as T

TINY = Path(__file__).resolve().parents[1] / "testdata" / "tiny.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def tr():
    return T.load(str(TINY))


def test_planes(tr):
    assert tr.devices == ["/device:TPU:0"]
    assert len(tr.ops["/device:TPU:0"]) == 12
    assert len(tr.modules["/device:TPU:0"]) == 6


def test_names():
    name = "%nm_spmm_2_8_u4.1 = f32[8,512]{1,0:T(8,128)} custom-call(bf16[8,1024])"
    assert T.instruction(name) == "nm_spmm_2_8_u4.1"
    assert T.kernel(name) == "nm_spmm_2_8_u4"
    assert T.program("jit_decode_fn(13238950607788813710)") == "decode_fn"


def test_busy_is_the_union_of_op_intervals(tr):
    # nm_spmm 3601 + 3621 + 3747; each mlp run: copy-start, then
    # copy-done and fusion.5 touching end to start (one interval):
    # 14 + 4103, 13 + 4140 - 2 gaps inside (333 + 3805), 14 + 4212
    assert tr and T.busy_s(tr) == pytest.approx(23463 * NS, abs=1e-12)


def test_busy_clipped_to_a_window(tr):
    win = (46_489_616 * NS, 47_129_138 * NS)   # first mlp to second nm_spmm
    assert T.busy_s(tr, win) == pytest.approx((14 + 4103 + 3621) * NS, abs=1e-12)


def test_program_time(tr):
    assert T.program_time(tr, "mlp") == (pytest.approx(12511 * NS, abs=1e-12), 3)
    assert T.program_time(tr, "_lambda") == (pytest.approx(10982 * NS, abs=1e-12), 3)
    assert T.program_time(tr, "decode_fn") == (0.0, 0)


def test_kernel_time(tr):
    secs, calls = T.kernel_time(tr, r"nm_spmm_\d+_\d+(_u4)?")
    assert calls == 3
    assert secs == pytest.approx(10969 * NS, abs=1e-12)
    assert T.kernel_time(tr, r"fused_update") == (0.0, 0)


def test_top_ops_are_self_times(tr):
    top = dict(T.top_ops(tr, 3))
    assert top["fusion.5"] == pytest.approx((3767 + 3805 + 3876) * NS, abs=1e-12)
    assert top["nm_spmm_2_8_u4.1"] == pytest.approx(10969 * NS, abs=1e-12)


def test_self_times_take_nested_ops_out():
    ev = [T.Event("%while.1 = x", 0.0, 10.0), T.Event("%fusion.2 = y", 1.0, 4.0),
          T.Event("%sort.3 = z", 5.0, 6.0), T.Event("%copy.4 = w", 12.0, 13.0)]
    assert T.self_times(ev) == {"while.1": 6.0, "fusion.2": 3.0, "sort.3": 1.0,
                                "copy.4": 1.0}


def test_idle_gaps_named_by_the_host(tr):
    ops = tr.ops["/device:TPU:0"]
    win = (ops[0].start, max(e.end for e in ops))
    gaps = T.idle_gaps(tr, win, 3)
    assert [g[0] for g in gaps] == ["host_gap"] * 3
    assert [g[1] for g in gaps] == pytest.approx(
        [3_599_015 * NS, 3_182_909 * NS, 2_667_607 * NS], abs=1e-12)


def test_program_times(tr):
    assert T.program_times(tr) == {
        "mlp": (pytest.approx(12511 * NS, abs=1e-12), 3),
        "_lambda": (pytest.approx(10982 * NS, abs=1e-12), 3)}
    win = (46_489_616 * NS, 47_129_138 * NS)   # first mlp to second nm_spmm
    assert {k: n for k, (_, n) in T.program_times(tr, win).items()} == {
        "mlp": 1, "_lambda": 1}


def test_step_mfu_reads_device_program_time(tr):
    from chipbench import spec

    ctx = {"trace": tr, "window": None, "log": lambda s: None,
           "peaks": {"bf16_flops_per_s": 1e12},
           "res": {"tokens": 10, "flops_per_token": {"sparse": 1e3}}}
    got = spec.metric_reader("train.step_mfu_pct")(ctx)
    assert got == pytest.approx(100 * 1e4 / ((12511 + 10982) * NS * 1e12))
    assert spec.metric_reader("train.step_mfu_pct")(dict(ctx, res={})) is None


def test_idle_needs_a_device_plane(tr):
    from chipbench import spec

    read = spec.metric_reader("train.device_idle_pct")
    assert read({"trace": tr, "window_s": 2.0, "busy_s": 0.5}) == 75.0
    assert read({"trace": T.Trace({}, {}, []), "window_s": 2.0, "busy_s": 0.0}) is None
