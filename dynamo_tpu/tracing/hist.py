"""A fixed geometric histogram for per-stream gaps: cheap enough to feed on
every token (one ``log``, one list index), small enough to ride a span.

``counts[0]`` holds the values below ``lo_s``, ``counts[i]`` those in
``[lo_s * ratio**(i-1), lo_s * ratio**i)`` and the last bucket everything
from ``hi_s`` up. The exported dict (``{"lo_s", "ratio", "counts"}``) says
all of that itself, so a reader of the span imports nothing from here:
bucket ``i`` ends at ``lo_s * ratio**i``. Histograms of one geometry add
bucket by bucket, which is how many streams' gaps become one tail.
"""

from __future__ import annotations

import math
from typing import List

LO_S = 1e-3
HI_S = 4.096
RATIO = 2.0 ** 0.25
_INV_LOG = 1.0 / math.log(RATIO)
_N = int(math.ceil(math.log(HI_S / LO_S) * _INV_LOG - 1e-9))   # 48


class GapHistogram:
    __slots__ = ("counts",)

    def __init__(self):
        self.counts: List[int] = [0] * (_N + 2)

    def add(self, x: float) -> None:
        if x < LO_S:
            i = 0
        else:
            i = min(1 + int(math.log(x / LO_S) * _INV_LOG), _N + 1)
        self.counts[i] += 1

    def to_dict(self) -> dict:
        return {"lo_s": LO_S, "ratio": RATIO, "counts": list(self.counts)}

