"""The fused causal attention path (``models/attention.py``).

On TPU, plain causal self-attention runs the Pallas splash kernel; every
other call runs ``chunked_attention``.  Here the fused wrapper runs in
Pallas interpret mode on the CPU against ``chunked_attention``, forward
and q/k/v gradients; the dispatch predicate is held to the path each
kind of call must take; and the shard_map layout is checked on four
virtual CPU devices in a child process.
"""

import contextlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from repro.models import attention as A
from repro.sharding import rules as R

ROOT = Path(__file__).resolve().parents[1]

# relative norm error, fused against chunked: fp32 inputs differ only in
# summation order (measured ~1e-6); bf16 inputs round p and the outputs
# and gradients to bf16 on both paths (eps 2**-8; measured ~4e-3)
RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}

SHAPES = {  # (query heads, kv heads, head dim, sequence)
    "gqa-d64-s256": (4, 2, 64, 256),
    "gqa-d128-s512": (4, 2, 128, 512),
    "mha-d64-s512": (4, 4, 64, 512),
    "mha-d128-s256": (4, 4, 128, 256),
    # several blocks a side (2 x 512, 3 x 256): blocks above the diagonal
    # skipped, partial masks on the diagonal, dq/dk/dv summed over blocks
    "gqa-d64-s1024": (4, 2, 64, 1024),
    "mha-d128-s1024": (4, 4, 128, 1024),
    "gqa-d128-s768": (4, 2, 128, 768),
    "mha-d64-s768": (4, 4, 64, 768),
}


def _qkv(h, hkv, d, s, dtype, b=2):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32).astype(dtype)
    ct = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    return q, k, v, ct


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_fused_matches_chunked(shape, dtype):
    q, k, v, ct = _qkv(*shape, dtype)

    def fused(q, k, v):
        return A.fused_causal_attention(q, k, v, interpret=True)

    def chunked(q, k, v):
        return A.chunked_attention(q, k, v, causal=True, q_offset=0,
                                   chunk_kv=128)

    def grads(attn):
        return jax.grad(lambda *a: (attn(*a).astype(jnp.float32) * ct).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    out_f, out_c = fused(q, k, v), chunked(q, k, v)
    assert out_f.dtype == q.dtype and out_f.shape == out_c.shape
    assert _rel(out_f, out_c) < RTOL[dtype]
    for name, gf, gc in zip("qkv", grads(fused), grads(chunked)):
        assert gf.dtype == dtype
        assert _rel(gf, gc) < RTOL[dtype], f"d{name}"


def _shapes(h=4, hkv=2, d=64, s=512, dv=None, b=2):
    sds = jax.ShapeDtypeStruct
    return (sds((b, s, h, d), jnp.bfloat16), sds((b, s, hkv, d), jnp.bfloat16),
            sds((b, s, hkv, dv or d), jnp.bfloat16))


DISPATCH = {  # case: (shapes, predicate keywords, fused?)
    "plain-causal-gqa": (_shapes(), {}, True),
    "plain-causal-mha-d128": (_shapes(4, 4, 128), {}, True),
    "short-s128": (_shapes(s=128), {}, True),
    "cpu": (_shapes(), {"backend": "cpu"}, False),
    "cpu-mesh": (_shapes(), {"mesh": "cpu"}, False),
    "mla-head-dims": (_shapes(4, 4, 192, dv=128), {}, False),
    "non-causal": (_shapes(), {"causal": False}, False),
    "kv-len-mask": (_shapes(), {"kv_len_mask": 100}, False),
    "q-offset": (_shapes(), {"q_offset": 128}, False),
    "s-not-block-multiple": (_shapes(s=320), {}, False),
    "s-under-128": (_shapes(s=64), {}, False),
    "head-dim-32": (_shapes(d=32), {}, False),
    "head-dim-over-256": (_shapes(d=320), {}, False),
}


@pytest.mark.parametrize("case", DISPATCH.values(), ids=DISPATCH.keys())
def test_predicate_picks_path(monkeypatch, case):
    """With the default backend reading ``backend`` (TPU unless the case
    says) in a one-device process; ``mesh`` runs the call under an
    active one-device mesh of this process's CPU, whose platform the
    predicate reads before the default backend."""
    (q, k, v), kw, fused = case
    kw = {"causal": True, "q_offset": 0, "kv_len_mask": None, **kw}
    backend, on_mesh = kw.pop("backend", "tpu"), kw.pop("mesh", None)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with contextlib.ExitStack() as stack:
        if on_mesh:
            mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                        ("data", "model"))
            stack.enter_context(R.activation_sharding(mesh, ("data",)))
        assert A.fused_path_ok(q, k, v, **kw) is fused


def test_sequence_parallel_takes_chunked():
    mesh = AbstractMesh((1, 1), ("data", "model"))
    with R.activation_sharding(mesh, ("data",), sp=True):
        assert A._kernel_layout(2, 4, 2) is None
    with R.activation_sharding(mesh, ("data",)):
        assert A._kernel_layout(2, 4, 2) == (None, None)


LAYOUTS = {  # case: (mesh axes, dp, (batch, heads, kv heads), spec or None)
    "batch-and-heads": ({"data": 2, "model": 2}, ("data",), (2, 4, 2),
                        P(("data",), "model", None, None)),
    "batch-only": ({"data": 4, "model": 1}, ("data",), (4, 4, 2),
                   P(("data",), None, None, None)),
    "pod-and-data-batch": ({"pod": 2, "data": 1, "model": 2}, ("pod", "data"),
                           (2, 4, 2), P(("pod", "data"), "model", None, None)),
    "kv-heads-do-not-divide": ({"data": 2, "model": 2}, ("data",), (2, 4, 1),
                               None),
    "batch-does-not-divide": ({"data": 2, "model": 2}, ("data",), (3, 4, 2),
                              None),
    "vmapped-pod-axis": ({"pod": 2, "data": 1, "model": 2}, ("data",),
                         (2, 4, 2), None),
    "one-device": ({"data": 1, "model": 1}, ("data",), (3, 5, 1),
                   (None, None)),
}


@pytest.mark.parametrize("case", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_kernel_layout_over_the_mesh(case):
    axes, dp, (b, h, hkv), want = case
    mesh = AbstractMesh(tuple(axes.values()), tuple(axes))
    with R.activation_sharding(mesh, dp):
        got = A._kernel_layout(b, h, hkv)
    if isinstance(want, P):
        want = (mesh, want)
    assert got == want


@pytest.mark.parametrize("devices,want", [(1, (None, None)), (4, None)],
                         ids=["one-device", "several-devices"])
def test_kernel_layout_without_a_mesh(monkeypatch, devices, want):
    """With no active mesh, a jit may span every device of the process:
    XLA cannot partition a Mosaic kernel, so only one device will do."""
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert A._kernel_layout(2, 4, 2) == want


def _attn_apply_paths(cfg, window=None):
    """Trace ``attn_apply`` (train, no cache) and count its paths."""
    from repro.core.sparsity import SparsityConfig

    p, _ = A.attn_init(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((2, 256, cfg.d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    sp = SparsityConfig(n=2, m=8, method="dense")
    with A.count_paths("attn_apply") as counts:
        jax.eval_shape(lambda x, pos: A.attn_apply(
            p, x, cfg, sp, positions=pos, layer_window=window)[0], x, pos)
    return dict(counts)


@pytest.mark.parametrize("case", [
    ("global", {}, None, {"fused": 1}),
    ("window", {}, 64, {}),
    ("mla", {"kv_lora": 32, "qk_nope_dim": 128, "qk_rope_dim": 64,
             "v_head_dim": 128}, None, {"chunked": 1}),
], ids=lambda c: c[0])
def test_attn_apply_on_a_tpu_backend(monkeypatch, case):
    """With the default backend reading TPU (and only traced, so nothing
    is lowered): a global layer takes the fused kernel, a sliding-window
    layer the banded scan (no dispatch site), MLA the chunked scan."""
    _, extra, window, want = case
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = A.AttnConfig(d_model=128, n_heads=4, n_kv=2, head_dim=64, **extra)
    assert _attn_apply_paths(cfg, window) == want


def test_cpu_train_step_reports_every_site_chunked(capsys):
    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.launch.mesh import make_host_mesh
    from repro.optim import sgd
    from repro.train import step as ST

    cfg = get_arch("qwen3-8b").smoke
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    bundle = ST.build_lm_train(cfg, make_host_mesh(), sp,
                               sgd.SGDConfig(total_steps=2))
    state = jax.eval_shape(lambda k: ST.init_train_state(k, cfg, sp_cfg=sp),
                           jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    bundle.step_fn.trace(state, {"tokens": tok, "labels": tok})
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("lm_train_step: attention sites traced")]
    assert lines == ["lm_train_step: attention sites traced: fused 0, chunked 1"]


CHILD = """
import json, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch import spmd
from repro.models import attention as A
from repro.sharding import rules as R

mesh = spmd.make_spmd_mesh("data=2,model=2")
ks = jax.random.split(jax.random.PRNGKey(0), 4)
b, s, h, hkv, d = 2, 1024, 4, 2, 64
q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
ct = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

def run(attn):
    def loss(q, k, v):
        with R.activation_sharding(mesh, ("data",)):
            return (attn(q, k, v) * ct).sum()
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

fused = run(lambda q, k, v: A.fused_causal_attention(q, k, v, interpret=True))
chunked = run(lambda q, k, v: A.chunked_attention(q, k, v, causal=True,
                                                  q_offset=0))
with R.activation_sharding(mesh, ("data",)):
    text = jax.jit(lambda q, k, v: A.fused_causal_attention(
        q, k, v, interpret=True)).lower(q, k, v).as_text()
rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
print("RESULT " + json.dumps({{
    "loss": rel(fused[0], chunked[0]),
    "grads": [rel(a, b) for a, b in zip(fused[1], chunked[1])],
    "shard_map": "shard_map" in text or "manual" in text}}))
"""


def test_shard_map_layout_on_four_devices():
    """Batch over ``data`` and heads over ``model`` on a 2 x 2 mesh of
    virtual CPU devices: the kernel, interpreted, per shard, matches the
    chunked scan, forward and gradients."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    assert res["shard_map"]
    assert res["loss"] < RTOL[jnp.float32]
    assert max(res["grads"]) < RTOL[jnp.float32]
