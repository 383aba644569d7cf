"""The least work of a cell's step, counted from the plan graph's shapes.

Nothing here depends on which backend implements a local FFT: the plan
graph (``analysis/plangraph.graph_for``) declares the same ``local_fft``
nodes and edges for xla, matmul and pallas, and the counts below read
only those nodes' axes and edge shapes.

- flops of a ``local_fft`` node: the nominal 2.5 * n * log2(extent) per
  transformed axis, n the logical real element count of the cube (the
  R2C convention; BASELINE.md, ``testing/workloads.flops_roundtrip_3d``);
- HBM bytes of a ``local_fft`` node: its input edge read once and its
  output edge written once (compulsory traffic);
- ICI bytes of an ``exchange`` node: what each chip sends, (P-1)/P of
  its share of the payload on the edge into the exchange.

Every count is per chip: the global graph's numbers over P.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, Iterable, Tuple

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS} (known: {sorted(table)})")
    return table[device_kind]


def nbytes(shape: Iterable[int], dtype: str) -> int:
    return int(np.prod(tuple(shape), dtype=np.int64)) * np.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class Work:
    """Per chip, for one step."""

    fft_flops: float = 0.0
    fft_hbm_bytes: float = 0.0
    ici_bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.fft_flops + other.fft_flops,
                    self.fft_hbm_bytes + other.fft_hbm_bytes,
                    self.ici_bytes + other.ici_bytes)

    def local_fft_s(self, pk: Dict[str, float]) -> float:
        """Least time of the local-FFT work: the larger of its flop and
        byte bounds."""
        return max(self.fft_flops / pk["bf16_flop_per_s"],
                   self.fft_hbm_bytes / pk["hbm_bytes_per_s"])

    def step_s(self, pk: Dict[str, float]) -> float:
        """Least time of the whole step: no resource can beat its own
        peak, however the work overlaps."""
        return max(self.fft_flops / pk["bf16_flop_per_s"],
                   self.fft_hbm_bytes / pk["hbm_bytes_per_s"],
                   self.ici_bytes / pk["ici_bytes_per_s"])


def graph_work(graph, global_shape: Tuple[int, ...], chips: int) -> Work:
    """Least work of one declared direction, per chip."""
    n_total = float(np.prod(global_shape, dtype=np.float64))
    flops = hbm = ici = 0.0
    for node in graph.nodes:
        if node.kind == "local_fft":
            flops += sum(2.5 * n_total * math.log2(global_shape[a])
                         for a in node.axes)
            hbm += sum(nbytes(e.shape, e.dtype) for e in graph.in_edges(node.id))
            hbm += sum(nbytes(e.shape, e.dtype) for e in graph.out_edges(node.id))
        elif node.kind == "exchange":
            p = node.axis_size
            # Each of the p chips sends (p-1)/p of its 1/p share.
            ici += sum(nbytes(e.shape, e.dtype) * (p - 1) / p
                       for e in graph.in_edges(node.id))
    return Work(flops / chips, hbm / chips, ici / chips)


def step_work(plan, calls: Iterable, chips: int) -> Work:
    """Least work of one step of ``calls`` (call files, each with its
    ``work``) on ``plan``, per chip."""
    total = Work()
    for call in calls:
        total = total + call.work(plan, chips)
    return total
