"""Logical work of the kernels the metrics put against the chip's peaks.

Counted from the real shapes of each call (N gallery rows, Q queries, D
features), never from the padded buckets, so a roofline share reads the
same work whatever implements it.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown device is an error."""
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def reid_match_multi_work(n: int, q: int, d: int) -> Tuple[float, float]:
    """FLOPs and HBM bytes of one ``reid_match_multi`` call.

    FLOPs: the (N, Q) cosine similarities, a multiply and an add per
    feature (2 N Q D), and the row norms and divisions of both operands
    (3 (N + Q) D: square, add, divide per element).  Bytes: float32
    gallery and queries read, the bool mask read, float32 scores and bool
    flags written."""
    flops = 2.0 * n * q * d + 3.0 * (n + q) * d
    nbytes = 4.0 * (n + q) * d + n * q + 4.0 * n * q + n * q
    return flops, nbytes


def roofline_seconds(calls: Iterable[Tuple[int, int, int]], peak: Dict[str, float]) -> float:
    """Least time the chip could take for ``calls`` of ``(N, Q, D)``: the
    larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, summed
    call by call."""
    total = 0.0
    for n, q, d in calls:
        flops, nbytes = reid_match_multi_work(n, q, d)
        total += max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return total
