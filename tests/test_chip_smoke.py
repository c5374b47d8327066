"""CPU rehearsal of ``chip_smoke.py --chips 4``'s sharded train checks.

The four-chip phase holds dense pod sync against the one-chip run and
compressed pod sync against ``two_pod_reference`` (the same semantics
written out on one device).  Here both run at the granite smoke size on
four virtual CPU devices, in a child process so the device count is set
before JAX starts.  Then the exchange is left out — ``ppermute`` returns
its input, so each pod keeps only its own gradient — and both checks
must refuse the result: the compressed run against the reference, and a
lossless (8 of 8) compressed run, i.e. dense sync without the cross-pod
reduction, against the one-chip run.

At smoke size single tokens weigh much more than at published widths:
sharding alone moves the losses by up to 0.24% here (dense sync against
one device; 3.8e-5 on TPU v5e at full width), so the rehearsal compares
at ``RTOL`` = 1%, with lr 0.1 from the first step, where a missing
exchange moves them by ~2%.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
RTOL = 1e-2

CHILD = """
import json, sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke as CS
from repro.configs import get_arch
from repro.optim.compress import GradCompressConfig

cfg = get_arch(CS.ARCH).smoke
opt = CS.sgd.SGDConfig(lr=0.1, warmup_steps=0, total_steps=4,
                       min_lr_frac=1.0)
kw = dict(batch=8, seq=64, steps=4, opt=opt)
out = CS.sharded_train_phase(cfg, 0, rtol={rtol}, **kw)
jax.lax.ppermute = lambda x, axis_name, perm: x   # exchange left out
mesh = CS.spmd.make_spmd_mesh("pod=2,data=1,model=2")
out["no_exchange"] = CS.train_run(cfg, mesh, 0, "no exchange",
                                  compress=True, **kw)["losses"]
out["no_exchange_lossless"] = CS.train_run(
    cfg, mesh, 0, "no exchange, 8 of 8", compress=True,
    grad_sync=GradCompressConfig(n=8, m=8), **kw)["losses"]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=str(ROOT), rtol=RTOL)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def cs():
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    return chip_smoke


def test_sharded_sync_passes_its_checks(rehearsal, cs):
    """The child ran ``sharded_train_phase``, checks included."""
    for key in ("solo", "dense_sync", "two_pod_reference",
                "compressed_sync"):
        assert len(rehearsal[key]) == 4
    cs.check_sync(rehearsal["dense_sync"], rehearsal["solo"], "dense", RTOL)
    cs.check_sync(rehearsal["compressed_sync"],
                  rehearsal["two_pod_reference"], "compressed", RTOL)


@pytest.mark.parametrize("run,want", [
    ("no_exchange", "two_pod_reference"),
    ("no_exchange_lossless", "solo"),
], ids=["compressed_check", "dense_check"])
def test_exchange_left_out_fails_the_check(rehearsal, cs, run, want):
    with pytest.raises(SystemExit, match="FAILED"):
        cs.check_sync(rehearsal[run], rehearsal[want], run, RTOL)
