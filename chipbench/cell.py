"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, the
cell names its configuration and traffic mix, and each of those (and each
per-layer metric and correctness limit) is a file of its own under
``chipbench/``, and so is each configuration's architecture
(``families/<name>.py``).  Adding a cell adds files and entries; no file
here changes.
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


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, here: Path = HERE) -> Callable[[object], Optional[float]]:
    """``metrics/<name>.py``'s ``read`` function."""
    return _load(here / "metrics" / f"{name}.py", f"metric_{name}").read


def family(conf: dict, here: Path = HERE):
    """The module ``families/<name>.py`` of the configuration's
    architecture: ``<name>`` is ``architectures[0]`` without its
    ``ForCausalLM`` suffix, lowercased (``Qwen2ForCausalLM``: ``qwen2``).
    It gives the program's model config, the served weights, the float32
    reference and the counts (``families/qwen2.py`` says what each is)."""
    arch = conf["architectures"][0]
    name = arch.removesuffix("ForCausalLM").lower()
    path = here / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family for architecture {arch!r}: "
                                f"{path} does not exist")
    return _load(path, f"family_{name}")
