"""Fused whole-cascade proxy scoring: a two-pass stacked GEMM.

This is the paper's hot loop — every record in the stream is scored by the
cascade's proxies.  Every proxy family lowers to the same packed depth-1
MLP form (see ``core/proxy_family.py``), so ONE kernel covers linear and
MLP stages alike:

    hid    = relu(x @ w1 + b1)        # hidden GEMM over ALL stages at once
    scores = hid @ w2 + b2            # block-diagonal readout GEMM
    mask   = scores >= thresholds

Linear stages occupy two hidden columns via the exact +/- trick
(``relu(z) - relu(-z) == z``); MLP stages occupy their true hidden width.
Feature standardization is folded into ``(w1, b1)`` at pack time, so the
kernel sees two affine maps and a relu — no per-stage branching.

Fusing both GEMMs, the bias adds, and the threshold comparison avoids four
HBM round-trips for the (N, H·P) and (N, P) intermediates; the (N, F)
record block is loaded into VMEM exactly once per cascade.

BlockSpec layout: grid over record tiles (bm rows); the stacked hidden dim
H·P and the stage dim P are each padded to the 128-lane width so both MXU
matmuls are aligned; F (feature dim, 64..1024) stays resident per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def interpret_default() -> bool:
    """Pallas interpret mode everywhere but a TPU backend: the compiled
    kernel exists only for the chip, the interpreter validates it on the
    CPU."""
    return jax.default_backend() != "tpu"


def _make_cascade_kernel(n_proxies, with_scores):
    """Fused whole-cascade tile kernel: hidden GEMM + relu, then the
    block-diagonal readout GEMM scores every stage column.

    Every operand is 2-D and the valid/mask tiles are int32: Mosaic
    lowers neither 1-D lane indexing nor ``bool`` vector refs.
    ``with_scores`` drops the score write the caller won't read (a full
    (block_m, Pp) HBM round-trip): the serving engine gates on masks
    alone.
    """

    def kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, thr_ref, scale_ref,
               valid_ref, *out_refs):
        x = x_ref[...]
        # weight loads dequantize in-register: int8 codes (quantized packed
        # cascade) widen to f32 on the way into the MXU — the HBM->VMEM
        # traffic is 1 byte/weight, the arithmetic stays f32
        hid = jnp.dot(x.astype(jnp.float32), w1_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        hid = jnp.maximum(hid + b1_ref[...], 0.0)
        # readout over the REAL stage columns only — the lane-pad columns
        # of w2 are all-zero and would multiply the second GEMM's cost by
        # ~128/P for nothing (the MXU pads the n-dim internally either way)
        s = jnp.dot(hid, w2_ref[...][:, :n_proxies].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        # the single dequantizing multiply: per-stage readout scales (all
        # ones for fp32 cascades — ``x * 1.0`` is an IEEE identity, so the
        # fp32 path stays bit-exact through this op)
        s = s * scale_ref[...][:, :n_proxies] + b2_ref[...][:, :n_proxies]
        keep = (s >= thr_ref[...][:, :n_proxies]) & (valid_ref[...] != 0)
        m = jnp.where(keep, 1, 0).astype(jnp.int32)
        pad = w2_ref.shape[1] - n_proxies
        if with_scores:
            out_refs[0][...] = jnp.pad(s, ((0, 0), (0, pad)))
        out_refs[-1][...] = jnp.pad(m, ((0, 0), (0, pad)))

    return kernel


def _pm_pack_linear_operands(w, b):
    """(F, P) affine stack -> two-pass operands via the +/- trick, h-major:
    columns [w | -w], readout (+1, -1) block-diagonal."""
    F, P = w.shape
    w1 = jnp.concatenate([w, -w], axis=1)  # (F, 2P): h-major [h=0 | h=1]
    b1 = jnp.concatenate([b, -b])
    eye = jnp.eye(P, dtype=jnp.float32)
    w2 = jnp.concatenate([eye, -eye], axis=0)  # (2P, P)
    return w1, b1, w2, jnp.zeros((P,), jnp.float32)


def proxy_score(x, w, b, thresholds, *, block_m: int = 256, interpret=None):
    """x: (N, F); w: (F, P); b, thresholds: (P,).

    Linear-stack convenience: returns (scores (N, P) f32, mask (N, P)
    bool).  Thin wrapper over the packed ``cascade_score`` — the +/- trick
    makes the two-pass scores bit-identical to the single affine map, so
    the pad/grid plumbing and kernel body exist exactly once.
    """
    w1, b1, w2, b2 = _pm_pack_linear_operands(jnp.asarray(w, jnp.float32),
                                              jnp.asarray(b, jnp.float32))
    scores, mask, _packed, _counts = cascade_score(
        x, w1, b1, w2, b2, thresholds, x.shape[0], block_m=block_m,
        interpret=interpret, with_scores=True, with_compaction=False,
    )
    return scores, mask


@functools.partial(jax.jit, static_argnames=(
    "block_m", "interpret", "with_scores", "with_compaction", "compact_cols"))
def cascade_score(x, w1, b1, w2, b2, thresholds, n_valid, *,
                  out_scale=None,
                  block_m: int = 256, interpret=None,
                  with_scores: bool = True, with_compaction: bool = True,
                  compact_cols=None):
    """One fused two-pass GEMM over a record tile for a whole cascade.

    x: (N, F) record tile (rows >= ``n_valid`` are padding and are masked
    out of every stage); w1: (F, HP) stacked folded hidden weights (HP =
    hidden bucket x stages, h-major — see
    ``core.proxy_family.cascade_kernel_operands``); b1: (HP,); w2:
    (HP, P) block-diagonal readout; b2, thresholds: (P,).

    ``out_scale`` (P,) are per-stage readout dequantization scales for
    weight-only-quantized cascades (``scores = readout * out_scale + b2``);
    None means ones — the fp32 path, bit-identical to the pre-quantization
    kernel (``x * 1.0`` preserves every bit).  ``w1``/``w2`` may be int8
    code matrices; they widen to f32 in-register after the VMEM load.
    ``interpret=None`` follows ``interpret_default()``.

    Returns:
      scores (N, P) f32          raw proxy scores (None if not with_scores)
      mask   (N, P) bool         per-stage keep masks (padding rows False)
      packed (C, N) int32        compacted survivor row indices per
                                 *assembled* stage: with ``compact_cols``
                                 a static tuple of column indices, C =
                                 len(compact_cols) and row ``c`` holds the
                                 ascending rows where mask[:, cols[c]] is
                                 True (tail -1); C = P when compact_cols is
                                 None (None if not with_compaction)
      counts (P,)  int32         survivors per stage, ALL columns (None
                                 when not with_compaction)

    Compaction runs on device, in XLA after the kernel: an exclusive
    prefix sum of the assembled columns' masks gives each survivor its
    packed slot and a single scatter writes the index lists, so a dense
    UDF batch index list exists without materialising the boolean mask
    on the host.  ``with_scores=False`` / ``with_compaction=False`` drop
    the outputs a caller won't read — the serving engine gates on masks
    alone.  ``compact_cols`` gates the scan and scatter per column: the
    executor consumes the packed list only for its first full-tile
    stage, so later columns' O(N) scatters are skipped instead of
    computed-then-discarded.
    """
    if interpret is None:
        interpret = interpret_default()
    N, F = x.shape
    HP = w1.shape[1]
    P = w2.shape[1]
    if out_scale is None:
        out_scale = jnp.ones_like(b2)
    pad_n = (-N) % block_m
    pad_hp = (-HP) % 128
    pad_p = (-P) % 128
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
    if pad_hp:
        w1 = jnp.pad(w1, ((0, 0), (0, pad_hp)))
        b1 = jnp.pad(b1, (0, pad_hp))
        w2 = jnp.pad(w2, ((0, pad_hp), (0, 0)))
    if pad_p:
        w2 = jnp.pad(w2, ((0, 0), (0, pad_p)))
        b2 = jnp.pad(b2, (0, pad_p))
        thresholds = jnp.pad(thresholds, (0, pad_p), constant_values=jnp.inf)
        out_scale = jnp.pad(out_scale, (0, pad_p), constant_values=1.0)
    Np, HPp, Pp = x.shape[0], w1.shape[1], w2.shape[1]
    valid = (jnp.arange(Np, dtype=jnp.int32) < n_valid).astype(jnp.int32)

    tile_spec = pl.BlockSpec((block_m, Pp), lambda i: (i, 0))
    row_spec = pl.BlockSpec((1, Pp), lambda i: (0, 0))
    out_specs = [tile_spec]
    out_shape = [jax.ShapeDtypeStruct((Np, Pp), jnp.int32)]
    if with_scores:
        out_specs.insert(0, tile_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct((Np, Pp), jnp.float32))
    outs = pl.pallas_call(
        _make_cascade_kernel(P, with_scores),
        grid=(Np // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, F), lambda i: (i, 0)),
            pl.BlockSpec((F, HPp), lambda i: (0, 0)),
            pl.BlockSpec((1, HPp), lambda i: (0, 0)),
            pl.BlockSpec((HPp, Pp), lambda i: (0, 0)),
            row_spec, row_spec, row_spec,
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, w1, b1[None, :], w2, b2[None, :], thresholds[None, :],
      out_scale[None, :], valid[:, None])
    scores = outs[0][:N, :P] if with_scores else None
    mask_p = outs[-1][:, :P] != 0
    if not with_compaction:
        return scores, mask_p[:N], None, None

    # exclusive scan of each assembled column gives every survivor its
    # packed slot; scatter rows to (stage, slot), dropping rejects.
    # Assembly runs only over the REAL P columns — the lane-pad columns
    # are all-False and would multiply the scatter cost ~128/P for
    # nothing — and, when ``compact_cols`` names the columns a caller
    # will actually consume, only over those.
    cols_sel = tuple(range(P)) if compact_cols is None else tuple(compact_cols)
    mask_sel = mask_p[:, jnp.asarray(cols_sel, jnp.int32)]  # (Np, C)
    C = len(cols_sel)
    mi = mask_sel.astype(jnp.int32)
    gpos = jnp.cumsum(mi, axis=0) - mi
    gpos = jnp.where(mask_sel, gpos, Np)  # sentinel slot -> dropped by scatter
    rows = jnp.broadcast_to(jnp.arange(Np, dtype=jnp.int32)[:, None], (Np, C))
    cols = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :], (Np, C))
    packed = jnp.full((C, Np), -1, jnp.int32).at[cols, gpos].set(
        rows, mode="drop")
    counts = jnp.sum(mask_p.astype(jnp.int32), axis=0)
    return scores, mask_p[:N], packed[:, :N], counts
