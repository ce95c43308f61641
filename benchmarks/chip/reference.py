"""Plain reference of what a served CORE query must produce.

Imports nothing of the program.  Two parts:

* ``orig_pass`` — the ORIG plan: every UDF on every record, then the
  conjunction of the query's predicates (``label IN values``).  The UDFs
  are the benchmark's own user code; the reference calls them in blocks.
* ``proxy_scores`` — a proxy cascade stage's score, straight from the
  stage's trained parameters in float64 on the host: a linear proxy is
  ``((x - mean) / scale) . w + b``, a one-hidden-layer MLP proxy is
  ``relu(((x - mean) / scale) @ w1 + b1) @ w2 + b2``.  It never reads the
  program's packed, folded or quantized operands.
* ``stated_scores`` — the same score computed at the precision the
  configuration states: float32 parameters with the standardizer folded
  in, and every matmul at JAX's default precision on the platform.  On a
  TPU that is one bfloat16 pass: each matmul rounds its operands to
  bfloat16 (round to nearest even), multiplies them exactly and sums in
  float32.  A proxy is a network of one hidden layer whose readout is a
  second matmul, so its hidden activations are rounded to bfloat16 too;
  a linear proxy's one hidden value is its affine score, so its score
  ends rounded to bfloat16.  On the CPU default precision is float32.

``decision_gaps`` compares gate decisions that the timed path produced
with float64 scores: a decision on the wrong side of the stage threshold
is measured by how far the reference score lies from the threshold, in
units of that stage's score spread over the window.  ``stated_flips``
counts the decisions that differ from ``stated_scores >= threshold``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def orig_pass(labels: Sequence[np.ndarray], values: Sequence[Sequence[int]]
              ) -> np.ndarray:
    """Rows passing every predicate: ``labels[k]`` are predicate k's UDF
    labels, ``values[k]`` the accepted classes."""
    ok = np.ones(len(labels[0]), bool)
    for lab, vals in zip(labels, values):
        ok &= np.isin(lab, list(vals))
    return ok


def proxy_scores(params, x: np.ndarray) -> np.ndarray:
    """float64 score of one proxy over ``x`` (N, F)."""
    x = np.asarray(x, np.float64)
    z = (x - np.asarray(params.mean, np.float64)) / np.asarray(
        params.scale, np.float64)
    if hasattr(params, "w1"):
        h = np.maximum(z @ np.asarray(params.w1, np.float64)
                       + np.asarray(params.b1, np.float64), 0.0)
        return (h @ np.asarray(params.w2, np.float64)).reshape(len(x)) \
            + float(np.asarray(params.b2, np.float64).reshape(-1)[0])
    return z @ np.asarray(params.w, np.float64).reshape(-1) \
        + float(np.asarray(params.b, np.float64).reshape(-1)[0])


def _bf16(a) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _matmul(a, b, bf16_operands: bool) -> np.ndarray:
    """One matmul at the stated precision: operands rounded to bfloat16
    or kept float32, products exact, the sum rounded to float32."""
    if bf16_operands:
        a, b = _bf16(a), _bf16(b)
    else:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (a @ b).astype(np.float32)


def stated_scores(params, x: np.ndarray, bf16_operands: bool) -> np.ndarray:
    """float32 score of one proxy at the configuration's stated precision."""
    f32 = np.float32
    x = np.asarray(x, f32)
    mean = np.asarray(params.mean, f32)
    scale = np.asarray(params.scale, f32)
    if hasattr(params, "w1"):
        w1 = np.asarray(params.w1, f32) / scale[:, None]
        b1 = (np.asarray(params.b1, f32) - (mean / scale) @ np.asarray(
            params.w1, f32)).astype(f32)
        hid = np.maximum(_matmul(x, w1, bf16_operands) + b1, f32(0))
        w2 = np.asarray(params.w2, f32).reshape(-1, 1)
        b2 = f32(np.asarray(params.b2, f32).reshape(-1)[0])
        return (_matmul(hid, w2, bf16_operands)[:, 0] + b2).astype(f32)
    w = (np.asarray(params.w, f32).reshape(-1) / scale).astype(f32)
    b = f32(float(np.asarray(params.b).reshape(-1)[0]) - float(mean @ w))
    z = (_matmul(x, w[:, None], bf16_operands)[:, 0] + b).astype(f32)
    return _bf16(z).astype(f32) if bf16_operands else z


def stated_flips(masks: np.ndarray, stated: np.ndarray,
                 thresholds: np.ndarray) -> int:
    """Gate decisions that differ from ``stated >= threshold`` (float32)."""
    want = stated >= np.asarray(thresholds, np.float32)[None, :]
    return int(np.count_nonzero(masks != want))


def decision_gaps(masks: np.ndarray, ref: np.ndarray, thresholds: np.ndarray,
                  spread: np.ndarray) -> np.ndarray:
    """Per decision: 0 where ``masks`` agrees with ``ref >= threshold``,
    else ``|ref - threshold| / spread`` of that column."""
    want = ref >= thresholds[None, :]
    wrong = masks != want
    gaps = np.abs(ref - thresholds[None, :]) / spread[None, :]
    return np.where(wrong, gaps, 0.0)
