"""Peaks of the chip and the operations and bytes of the kernels.

``peaks(device_kind)`` reads ``bench/peaks.json``; a device that is not in
the table is an error, never a default.  ``gather_distance_cost`` counts what
one call of the Pallas ``gather_distance`` kernel (``kernels/gather_dist.py``)
must move and compute, from its shapes alone:

* bytes: every candidate row it DMAs (``B × C_pad`` rows of ``d`` elements;
  padding lanes fetch row 0, so they count), the query rows, the ids (read
  twice: the SMEM copy that drives the DMAs and the VMEM copy that masks),
  the gathered norms and the distances written;
* operations: the ``q·x`` contraction, 2·d per candidate, which ``highest``
  precision runs as six bf16 passes on the MXU, plus the l2 epilogue
  (4 per candidate).

The kernel reads 0.5 operations per byte, far below the v5e's ridge of
about 240, so its roofline is the HBM bandwidth: ``roofline_share`` says
which bound applied.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
F32_HIGHEST_PASSES = 6  # bf16 MXU passes per f32 product at Precision.HIGHEST


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def gather_distance_cost(b: int, c_pad: int, d: int, elem_bytes: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one ``gather_distance`` call on a (b, c_pad)
    candidate table of rows ``d`` wide."""
    rows = b * c_pad * d * elem_bytes
    small = b * d * 4 + b * c_pad * 4 * 4  # q; ids twice, norms, distances
    ops = b * c_pad * (2 * d + 4)
    return float(ops), float(rows + small)


def roofline_share(ops: float, nbytes: float, seconds: float, device_kind: str,
                   passes: int = F32_HIGHEST_PASSES) -> tuple[float, str]:
    """(percent of the roofline, which bound applies): the least time the
    chip needs for ``ops`` and ``nbytes``, over the measured ``seconds``."""
    if seconds <= 0:
        raise ValueError("kernel time must be positive")
    p = peaks(device_kind)
    t_compute = ops * passes / p["bf16_flops_per_s"]
    t_memory = nbytes / p["hbm_bytes_per_s"]
    bound = "memory" if t_memory >= t_compute else "compute"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
