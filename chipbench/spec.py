"""What a cell is, found by name: BENCHMARK.json, configuration files,
traffic files, per-layer metric readers and the table of peaks.

Nothing here imports JAX or the program, so the tests and the harness's
argument handling load it without touching a device.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    return load_json(path)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def config(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "configs" / f"{name}.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "traffic" / f"{name}.json")


def limits(workload: str, root: Path = ROOT) -> dict:
    """{number compared: its limit} of one cell (``cells/<name>.json``)."""
    return load_json(root / "cells" / f"{workload}.json")["limits"]


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip of this kind; a kind not in the table
    is an error, never a default."""
    table = load_json(root / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """Metric entries this cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer metrics."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a configuration file, under the benchmark's own names.
    The reference and the operation counts read only this."""

    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    d_ff: int = 0
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    tie_embed: bool = True
    qk_norm: bool = False
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    pad_vocab_to: int = 256
    capacity_factor: float = 1.25
    group_size: int = 512

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.pad_vocab_to) * self.pad_vocab_to

    @property
    def moe(self) -> bool:
        return self.n_experts > 0


def model(cfg: dict) -> Model:
    """Configuration file (Hugging Face key names) -> Model."""
    heads = cfg["num_attention_heads"]
    experts = cfg.get("num_local_experts", 0)
    return Model(
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=heads, n_kv=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        vocab=cfg["vocab_size"],
        d_ff=0 if experts else cfg["intermediate_size"],
        n_experts=experts, top_k=cfg.get("num_experts_per_tok", 0),
        d_expert=cfg["intermediate_size"] if experts else 0,
        tie_embed=cfg["tie_word_embeddings"],
        qk_norm=cfg.get("qk_norm", False), rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"],
        pad_vocab_to=cfg["program"]["pad_vocab_to"],
        capacity_factor=cfg["program"].get("moe_capacity_factor", 1.25),
        group_size=cfg["program"].get("moe_group_size", 512))
