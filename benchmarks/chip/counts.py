"""Work a served query requires, counted from the plan's shapes, and the
chip peaks it is measured against.

Every count here is the work of the model as the plan states it, never
the work of one implementation's layout: padding rows to a compile
bucket, padding lanes to 128, the +/- column pair a linear proxy takes in
the packed kernel, or the rows a UDF batch is padded to are all left out,
so a later kernel that changes its layout is read against the same work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


def proxy_hidden_width(params) -> int:
    """Hidden width of one proxy as trained: 1 for a linear proxy (one
    dot product), the hidden layer's width for an MLP proxy."""
    if hasattr(params, "w1"):
        return int(params.w1.shape[1])
    return 1


def cascade_flops(rows: int, n_features: int, hidden: Sequence[int]) -> float:
    """FLOPs to score ``rows`` records through every proxy column:
    ``2·rows·F·ΣH`` for the hidden products plus ``2·rows·ΣH`` for the
    readout.  Padded rows and lanes are not work."""
    h = float(sum(hidden))
    return 2.0 * rows * n_features * h + 2.0 * rows * h


def cascade_bytes(rows: int, calls: int, n_features: int,
                  hidden: Sequence[int], weight_bytes: int = 4) -> float:
    """HBM bytes the cascade needs at the least: each record's float32
    features read once, the weights read once per call, and one keep byte
    written per record and column."""
    h = float(sum(hidden))
    p = len(hidden)
    weights = (n_features * h + 2.0 * h + 2.0 * p) * weight_bytes
    return 4.0 * rows * n_features + calls * weights + 1.0 * rows * p


def mlp_flops(rows: int, dims: Sequence[int]) -> float:
    """FLOPs of an MLP forward over ``rows`` unpadded records:
    ``2·Σ d_i·d_{i+1}`` per record (bias adds and activations left out)."""
    per = sum(dims[i] * dims[i + 1] for i in range(len(dims) - 1))
    return 2.0 * rows * per


def least_time_s(flops: float, nbytes: float, peaks: dict):
    """The roofline: (seconds, bound) where seconds is the larger of
    FLOPs over the bf16 peak and bytes over the HBM bandwidth."""
    t_flops = flops / peaks["bf16_flop_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
