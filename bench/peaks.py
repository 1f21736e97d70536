"""Peaks of the chips the benchmark runs on, and the operations and bytes
that a kernel call needs, computed from its shapes.

A roofline share is the least time the chip could take for the call, the
larger of ``ops / peak ops/s`` and ``bytes / peak bytes/s``, over the time
the trace measured.  A device kind missing from ``PEAKS`` is an error.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16), 393 TOP/s
# (int8), 16 GB of HBM at 819 GB/s.  JAX names the chip "TPU v5 lite".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"ops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def reduced_top2_cost(batch: int, n: int) -> Tuple[float, float]:
    """``(ops, bytes)`` of one ``reduced_top2`` call on ``cost (batch, n,
    n)`` and ``prices (batch, n)``, all 32-bit: each cost element is read
    once and takes five operations (the price add, the minimum, the
    argmin, the mask of the argmin and the second minimum); the prices are
    read and three ``(batch, n)`` rows (min, argmin, second min) written."""
    elems = batch * n * n
    return 5.0 * elems, 4.0 * (elems + batch * n + 3 * batch * n)


_SHAPE = re.compile(r"f32\[([0-9,]+)\]")


def square_operand(text: str) -> Optional[Tuple[int, int]]:
    """``(batch, n)`` of the first 32-bit float operand in ``text`` whose
    last two dimensions are equal (``f32[b, ..., n, n]``), its leading
    dimensions multiplied into the batch."""
    for m in _SHAPE.finditer(text):
        dims = [int(x) for x in m.group(1).split(",") if x]
        if len(dims) >= 3 and dims[-1] == dims[-2]:
            batch = 1
            for d in dims[:-2]:
                batch *= d
            return batch, dims[-1]
    return None


def least_time(ops: float, nbytes: float, device_kind: str
               ) -> Tuple[float, str]:
    """The least time for ``ops`` and ``nbytes`` on the chip, and which
    bound sets it (``"compute"`` or ``"memory"``)."""
    p = peaks(device_kind)
    t_ops, t_bytes = ops / p["ops_per_s"], nbytes / p["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
