"""Finds a cell's files by name: ``BENCHMARK.json`` names the cell, and the
cell's own file under ``bench/cells/`` names its configuration
(``bench/configs/``), its traffic mix (``bench/traffic/``) and the engine
settings a user would pass.  Adding a cell adds files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
BENCHMARK = ROOT / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<mix>.json
    engine: dict  # EngineCore keyword arguments
    check: dict  # {"requests": sample size, "limit": widest logit gap allowed}
    end_to_end: tuple  # BENCHMARK.json end-to-end metrics this cell reports
    per_layer: tuple  # BENCHMARK.json per-layer metrics this cell reports


def _read(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` lists it, with its files."""
    spec = _read(BENCHMARK)
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in {BENCHMARK}; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    entry = entries[0]
    cell = _read(BENCH / "cells" / f"{name}.json")
    if (cell["config"], cell["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: the cell file names {cell['config']}/{cell['traffic']}, "
                         f"BENCHMARK.json {entry['config']}/{entry['traffic']}")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_read(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=_read(BENCH / "traffic" / f"{entry['traffic']}.json"),
        engine=dict(cell["engine"]),
        check=dict(cell["check"]),
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)),
    )


# bench config key -> repro ModelConfig field
_MODEL_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a bench configuration: the
    program's own entry for ``arch``, with every size the bench file states
    put in its place, so the program runs exactly what the file says."""
    from repro.configs import get_config
    from repro.configs.base import QuantConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{config['name']}: only SwiGLU blocks are served")
    base = get_config(config["arch"])
    fields = {f: config[k] for k, f in _MODEL_FIELDS.items() if k in config}
    mode = "ternary" if config["weights"] == "ternary" else "bf16"
    return dataclasses.replace(base, quant=QuantConfig(mode=mode), norm="rmsnorm",
                               act="silu", moe=False, sliding_window=None, **fields)
