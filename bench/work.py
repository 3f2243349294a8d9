"""Operations and bytes the algorithm needs, computed from shapes, and the
table of device peaks.  These are the yardstick of the utilization and
roofline metrics; what the program happens to execute does not enter them.
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


def cnn_forward_flops(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Multiply-add FLOPs (2 per MAC) of one sample's forward pass, by layer:
    conv 3×3 SAME at full resolution, then at half, then the two dense
    layers.  Biases, activations and pooling are not counted."""
    s, ch = cfg["image_size"], cfg["channels"]
    c1, c2, hid, ncls = cfg["conv1"], cfg["conv2"], cfg["hidden"], cfg["num_classes"]
    flat = (s // 4) ** 2 * c2
    return {"conv1": 2 * s * s * c1 * 9 * ch,
            "conv2": 2 * (s // 2) ** 2 * c2 * 9 * c1,
            "fc1": 2 * flat * hid,
            "fc2": 2 * hid * ncls}


def cnn_train_flops_per_sample(cfg: Dict[str, Any]) -> int:
    """Forward + backward FLOPs one trained sample requires: the forward, the
    weight gradient of every layer (as much again), and the input gradient
    of every layer but the first (the image needs none)."""
    fwd = cnn_forward_flops(cfg)
    total = sum(fwd.values())
    return 3 * total - fwd["conv1"]


def cnn_num_params(cfg: Dict[str, Any]) -> int:
    s, ch = cfg["image_size"], cfg["channels"]
    c1, c2, hid, ncls = cfg["conv1"], cfg["conv2"], cfg["hidden"], cfg["num_classes"]
    flat = (s // 4) ** 2 * c2
    return (9 * ch * c1 + c1 + 9 * c1 * c2 + c2 + flat * hid + hid
            + hid * ncls + ncls)


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
