"""Arithmetic of a measured window, from what the client saw.

Every request is a ``Stream``: when it was due, when the generator sent
it, and the host-clock time of each delta the engine handed back.  The
window is ``[t0, t1]``.  All of its requests and gaps count:

* time to first token is taken for every request due inside the window;
  one that has no token by ``t1`` enters with its wait so far;
* a gap between output tokens counts when its later token arrives inside
  the window; a stream still decoding at ``t1`` adds its open gap up to
  ``t1``, so a stall at the end is never dropped; the tokens after the
  first in a multi-token delta have gap 0;
* an open loop times each request from when it was due, not from when the
  generator got round to sending it; ``lateness`` says how late that was.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Stream:
    rid: str
    due: float  # host clock: when the request was due to be sent
    sent: float  # host clock: when the generator submitted it
    prompt_len: int
    emits: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    failed: bool = False


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def due_in(streams, t0: float, t1: float) -> list:
    return [s for s in streams if t0 <= s.due < t1]


def ttft(streams, t0: float, t1: float) -> List[float]:
    """Seconds from due to first token for requests due in the window."""
    out = []
    for s in due_in(streams, t0, t1):
        first = s.emits[0][0] if s.emits and s.emits[0][0] <= t1 else t1
        out.append(first - s.due)
    return out


def itl(streams, t0: float, t1: float) -> List[float]:
    """Gaps between consecutive output tokens, as set out above."""
    gaps = []
    for s in streams:
        prev = None
        for t, n in s.emits:
            if t > t1:
                break
            if prev is not None and t > t0:
                gaps.append(t - prev)
                gaps.extend([0.0] * (n - 1))
            elif prev is None and t > t0:
                gaps.extend([0.0] * (n - 1))  # the first delta's extra tokens
            prev = t
        open_end = s.done is None or s.done > t1
        if prev is not None and open_end and not s.failed and t1 > max(prev, t0):
            gaps.append(t1 - prev)
    return gaps


def tokens(streams, t0: float, t1: float) -> int:
    """Output tokens handed to clients inside the window."""
    return sum(n for s in streams for t, n in s.emits if t0 < t <= t1)


def lateness(streams, t0: float, t1: float) -> List[float]:
    """Seconds each request of the window was sent after it was due."""
    return [s.sent - s.due for s in due_in(streams, t0, t1)]
