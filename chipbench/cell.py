"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, the
cell names its configuration and traffic mix, and each of those (and each
per-layer metric and correctness limit) is a file of its own under
``chipbench/``.  Adding a cell adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, here: Path = HERE) -> dict:
    return _json(here / "traffic" / f"{name}.json")


def limits(cell: str, here: Path = HERE) -> dict:
    return _json(here / "limits" / f"{cell}.json")


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, here: Path = HERE) -> Callable[[object], Optional[float]]:
    """``metrics/<name>.py``'s ``read`` function."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Qwen2 configuration file."""
    from repro.configs import ModelConfig
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or d // h,
        rope_theta=float(conf["rope_theta"]),
        qkv_bias=bool(conf["attention_bias"]),
        rms_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        source=conf["source"], dtype=conf["torch_dtype"])
