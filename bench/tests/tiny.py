"""Tiny cells for the bench's CPU tests: the published block shapes at toy
widths, with the engine settings of the real cells scaled down."""
import copy
import json
from pathlib import Path

from bench.harness import spec

BENCH = Path(__file__).resolve().parents[1]


def config(name: str, **sizes) -> dict:
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update(hidden_size=64, num_hidden_layers=2, head_dim=16, intermediate_size=128,
             vocab_size=250)
    if name == "bitnet-730m":
        c.update(num_attention_heads=4, num_key_value_heads=4)
    else:
        c.update(num_attention_heads=4, num_key_value_heads=2)
    c.update(sizes)
    return c


CLOSED = {"loop": "closed", "clients": 2, "requests_per_client": 3, "order_seed": 0,
          "prompt_len": {"dist": "uniform", "lo": 40, "hi": 70},
          "output_len": {"dist": "uniform", "lo": 8, "hi": 16}, }
OPEN = {"loop": "open", "rate": 20.0, "pre_roll_s": 0.3, "order_seed": 0,
        "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.5, "lo": 20, "hi": 80},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 4, "hi": 16},
        }
CONTIGUOUS = {"mode": "pdswap", "cache_layout": "contiguous", "kv_dtype": "fp", "n_slots": 2,
              "max_len": 96, "prompt_len": 16, "prefill_chunk": 16, "swap_policy": "drain"}
PAGED = {"mode": "pdswap", "cache_layout": "paged", "block_size": 8, "kv_dtype": "fp",
         "n_slots": 3, "max_len": 96, "prompt_len": 16, "prefill_chunk": 16,
         "swap_policy": "drain"}

# widest logit gap allowed at these sizes: the engine and the reference
# agree to float32 rounding on the CPU, while the controls read 0.07 and more
LIMIT = 0.02


def cell(config_name="bitnet-730m", mix=CLOSED, engine=CONTIGUOUS, limit=LIMIT,
         **sizes) -> spec.Cell:
    real = spec.load_cell("bitnet-730m.longdoc_decode")
    return spec.Cell(name=f"tiny-{config_name}", chips=1, config=config(config_name, **sizes),
                     traffic=copy.deepcopy(mix), engine=dict(engine),
                     check={"requests": 6, "limit": limit},
                     end_to_end=real.end_to_end, per_layer=real.per_layer)
