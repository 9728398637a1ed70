"""The work a step needs and the least time the chip could take for it.

A configuration's family module (``bench.harness.spec.family``) counts the
work from the model's equations; here are the units it counts in and the
storage tables it counts with.  Operations are split by the peak they run
at: int8 arithmetic (W1.58-A8's ternary linears) and bfloat16.
"""
from __future__ import annotations

import dataclasses

KV_BYTES = {"fp": 2, "int8": 1, "int4": 0.5}  # per cached element, by the cell's kv_dtype
TERNARY_BITS = 2  # the packed storage of one ternary weight


@dataclasses.dataclass(frozen=True)
class Work:
    int8_ops: float = 0.0
    bf16_flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.int8_ops + o.int8_ops, self.bf16_flops + o.bf16_flops,
                    self.bytes + o.bytes)

    def seconds(self, peak) -> float:
        """The least time the chip could take: compute or memory, whichever
        bounds it."""
        compute = self.int8_ops / peak.int8_ops + self.bf16_flops / peak.bf16_flops
        return max(compute, self.bytes / peak.hbm_bw)
