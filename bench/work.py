"""Operations and bytes the algorithm needs, computed from shapes, and the
table of device peaks.  These are the yardstick of the utilization and
roofline metrics; what the program happens to execute does not enter them.

This module holds what every client model shares: the peaks, the roofline,
and the work of the two kernels of the FL round, which read label plans and
flat parameter stacks.  A model's own counts (its parameters, its training
FLOPs) live in its reference module, ``bench/references/<name>.py``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(table)} — add the device with its source")
    return table[device_kind]


def label_hist_bytes(clients: int, samples: int, classes: int) -> int:
    """Least bytes one histogram pass moves: int32 labels and a one-byte
    mask read, the (clients, classes) f32 counts written."""
    return clients * samples * (4 + 1) + clients * classes * 4


def weighted_agg_bytes(clients: int, params: int) -> int:
    """Least bytes of one masked weighted mean over ``clients`` f32 models:
    the stack read, the weights read, the mean written."""
    return clients * params * 4 + clients * 4 + params * 4


def weighted_agg_flops(clients: int, params: int) -> int:
    return 2 * clients * params


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, Any]) -> tuple:
    """(share of the roofline in %, the bound that applies): the least time
    the chip could take for the work over the time it took."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
