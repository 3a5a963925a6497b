"""The transform's own work, and the least time a chip could take for it.

The work of a batched complex transform of ``b`` signals of ``n`` points is
fixed by ``(b, n, dtype)`` alone, whatever implements it:

* flops: ``5 * b * n * log2(n)``, the standard FFT convention;
* bytes: one HBM read and one HBM write of the operand,
  ``2 * b * n * itemsize`` (the paper's bandwidth metric, section 5.1.2).

So no change of radix, pass count, fusion or kernel can make the count
stale, and a share of the roofline cannot pass 100% unless the time leaves
out part of the work. The peaks come from ``peaks.json``, keyed by JAX's
``device_kind``; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
import math
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def transform_work(b: int, n: int, itemsize: int) -> dict:
    """``{"flops", "bytes"}`` of a forward transform of ``b`` complex
    signals of ``n`` points, ``itemsize`` bytes per complex element."""
    if b < 1 or n < 2:
        raise ValueError(f"need b >= 1 and n >= 2, got b={b}, n={n}")
    return {"flops": 5.0 * b * n * math.log2(n),
            "bytes": 2.0 * b * n * itemsize}


def load_peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """The peak table's entry for ``device_kind``; raises ``KeyError`` for a
    device the table does not hold."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the table "
                       f"holds {sorted(table)}")
    return table[device_kind]


def least_time(work: dict, peaks: dict, chips: int = 1) -> tuple[float, str]:
    """``(seconds, bound)``: the larger of bytes over HBM bandwidth and flops
    over the FLOP peak, for each chip's share of ``work`` on one chip;
    ``bound`` names which of the two sets it (``"hbm"`` or ``"flops"``)."""
    t_mem = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    t_flop = work["flops"] / chips / peaks["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")


def roofline_pct(work: dict, peaks: dict, device_s: float,
                 chips: int = 1) -> float:
    """The least time as a percentage of ``device_s``, the measured device
    time per call on one chip."""
    if device_s <= 0:
        raise ValueError(f"device time must be positive, got {device_s}")
    return 100.0 * least_time(work, peaks, chips)[0] / device_s
