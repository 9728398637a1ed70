"""Finds a cell's files by name: ``BENCHMARK.json`` names the cell, and the
cell's own file under ``bench/cells/`` names its configuration
(``bench/configs/``), its traffic mix (``bench/traffic/``) and the engine
settings a user would pass.  Adding a cell adds files; nothing here changes.

Whatever depends on the model's equations comes from the configuration's
family module: the file its ``"reference"`` key names, under ``bench/``
(:func:`family`).  A family module supplies

* ``model_fields(c) -> dict``: the ``ModelConfig`` fields the program takes,
  put over the program's own entry for ``c["arch"]``;
* ``layout(c)``: ``(path, shape, init)`` for every weight leaf, in the order
  the leaves are drawn from the seed.  ``path`` is a tuple of keys into the
  tree the engine takes, a leaf of any rank, in ``layers`` or outside it.
  ``init`` is ``("normal", std)``, ``("gain",)``, ``("bias",)``, or
  ``("embed", std)`` / ``("head", std)`` for the vocabulary's rows of the
  embedding (axis 0) and columns of the head (axis 1), which
  ``weights.make_weights`` pads;
* the plain float32 reference: ``logits(weights, c, tokens, positions,
  precision="reference")``, ``gaps(ref_logits, tokens)``, ``Q_BLOCK`` (rows
  ``logits`` pads its positions to) and ``CONTROLS`` (the lower precisions
  ``logits`` takes);
* the roofline work, in ``costs.Work``: ``weight_bytes(c)``,
  ``kv_bytes_per_token(c, kv_dtype)``, ``decode(c, stats, kv_dtype)``, the
  work of the window's decode rounds from its counter deltas (``stats``:
  every counter of the engine's ``EngineStats``), and ``prefill(c,
  prompt_len)``, one prompt's prefill.

Like the reference, a family module imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import importlib
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


def family(config: dict):
    """The family module that ``config["reference"]`` names, a ``.py`` file
    under ``bench/``, imported once per path (as ``bench.<dir>.<file>``)."""
    name = config.get("name", "?")
    if "reference" not in config:
        raise KeyError(f"configuration {name!r} has no 'reference' key naming its family module")
    rel = config["reference"]
    path = (ROOT / rel).resolve()
    if not path.is_relative_to(BENCH) or path.suffix != ".py":
        raise ValueError(f"configuration {name!r}: reference {rel!r} is not a .py file under "
                         f"{BENCH}")
    if not path.is_file():
        raise FileNotFoundError(f"configuration {name!r}: reference {rel!r} is missing")
    return importlib.import_module(".".join(path.relative_to(ROOT).with_suffix("").parts))


def model_config(config: dict):
    """The program's ``ModelConfig`` for a bench configuration: the
    program's own entry for ``arch``, with the fields the configuration's
    family gives put in their place, so the program runs exactly what the
    file says."""
    from repro.configs import get_config
    from repro.configs.base import QuantConfig

    mode = "ternary" if config["weights"] == "ternary" else "bf16"
    return dataclasses.replace(get_config(config["arch"]), quant=QuantConfig(mode=mode),
                               **family(config).model_fields(config))
