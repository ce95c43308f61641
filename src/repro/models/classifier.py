"""Sequence classifier over an MLA + MoE decoder backbone (DeepSeek-V3 /
Moonlight layout), served as an ML UDF.

tokens (B, S) int32, right-padded with ``PAD_ID`` ->
embedding -> ``first_dense`` layers of causal MLA + dense gated MLP ->
the remaining layers of causal MLA + ``moe.held_experts_apply`` ->
final RMSNorm -> mean over the valid (non-pad) tokens -> linear head to
``n_classes`` float32 logits.

Pad tokens run through every layer like any token.  Attention is causal
and the padding is on the right, so no valid token attends to a pad
token, and the pooling leaves them out: a record's logits do not depend
on its padding or its batch neighbours.  The expert layer holds the
routed experts ``[held_first, held_first + n_held)`` of an
expert-parallel deployment and computes their part of the result only
(see ``moe.held_experts_apply``).

``classify`` also returns the call's counts as one int32 vector (laid out
by ``stat_layout``): token slots, valid tokens, causal (query, key) pairs
among valid tokens, and per MoE layer and held expert the assignments of
all tokens and of the valid ones.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE

PAD_ID = 0


def n_moe_layers(cfg) -> int:
    return cfg.num_layers - cfg.moe.first_dense


def init(key, cfg, *, n_held: int, n_classes: int):
    """Seeded weights in ``cfg.dtype`` (norms, the correction bias and the
    head in float32).  The head starts at zero: it is fitted afterwards."""
    m = cfg.moe
    k_emb, k_dense, k_moe = jax.random.split(key, 3)
    dt = L.param_dtype(cfg)

    def dense_layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": L.init_rms_for(cfg, cfg.d_model),
                "attn": MLA.init_mla(k1, cfg),
                "ln2": L.init_rms_for(cfg, cfg.d_model),
                "mlp": L.init_mlp(k2, cfg, d_ff=m.dense_ff)}

    def moe_layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": L.init_rms_for(cfg, cfg.d_model),
                "attn": MLA.init_mla(k1, cfg),
                "ln2": L.init_rms_for(cfg, cfg.d_model),
                "experts": MOE.init_held_experts(k2, cfg, n_held)}

    params = {
        "embed": L.dense_init(k_emb, (cfg.vocab_size, cfg.d_model),
                              in_axis=-1, dtype=dt),
        "final_norm": L.init_rms_for(cfg, cfg.d_model),
        "head_w": jnp.zeros((cfg.d_model, n_classes), jnp.float32),
        "head_b": jnp.zeros((n_classes,), jnp.float32),
    }
    if m.first_dense:
        params["dense_layers"] = L.stack_init(dense_layer, k_dense, m.first_dense)
    params["layers"] = L.stack_init(moe_layer, k_moe, n_moe_layers(cfg))
    return params


def pooled(params, cfg, tokens, *, held_first: int):
    """(pooled (B, D) float32, stats (int32 vector, ``stat_layout``))."""
    B, S = tokens.shape
    n_held = params["layers"]["experts"]["wg"].shape[1]
    valid = tokens != PAD_ID
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    flat_valid = valid.reshape(-1)

    def attend(lp, h):
        return h + MLA.mla_attend(lp["attn"], cfg, L.apply_norm(cfg, h, lp["ln1"]),
                                  positions)

    def dense_body(h, lp):
        h = attend(lp, h)
        return h + L.mlp_apply(lp["mlp"], cfg, L.apply_norm(cfg, h, lp["ln2"])), None

    def moe_body(h, lp):
        h = attend(lp, h)
        hn = L.apply_norm(cfg, h, lp["ln2"]).reshape(B * S, -1)
        out, top_e = MOE.held_experts_apply(lp["experts"], cfg, hn, held_first)
        loads = jnp.stack([MOE.held_load(top_e, held_first, n_held),
                           MOE.held_load(top_e, held_first, n_held, flat_valid)])
        return h + out.reshape(h.shape), loads

    h = params["embed"][tokens]
    if cfg.moe.first_dense:
        h, _ = lax.scan(dense_body, h, params["dense_layers"])
    h, loads = lax.scan(moe_body, h, params["layers"])  # loads (L, 2, n_held)
    h = L.apply_norm(cfg, h, params["final_norm"]).astype(jnp.float32)
    lengths = jnp.sum(valid, axis=1, dtype=jnp.int32)
    pool = jnp.sum(jnp.where(valid[..., None], h, 0.0), axis=1) / jnp.maximum(
        lengths, 1)[:, None].astype(jnp.float32)
    stats = jnp.concatenate([
        jnp.asarray([B * S], jnp.int32),
        jnp.sum(lengths, keepdims=True),
        jnp.sum(lengths * (lengths + 1) // 2, keepdims=True),
        loads[:, 0].reshape(-1), loads[:, 1].reshape(-1)])
    return pool, stats


def head(params, pool):
    """float32 logits of the pooled states, at full float32 precision."""
    return jnp.dot(pool, params["head_w"],
                   precision=lax.Precision.HIGHEST) + params["head_b"]


def classify(params, cfg, tokens, *, held_first: int):
    """(logits (B, n_classes) float32, stats int32 vector)."""
    pool, stats = pooled(params, cfg, tokens, held_first=held_first)
    return head(params, pool), stats


def row_forward(cfg, *, held_first: int, token_col: int):
    """The jitted batch program of a UDF over float32 record rows whose
    columns from ``token_col`` on hold token ids: ``classifier_forward(
    params, rows) -> (logits, stats)``.  Weights are arguments, so one
    compiled program per row count serves every UDF of this shape."""
    def classifier_forward(params, rows):
        return classify(params, cfg, rows[:, token_col:].astype(jnp.int32),
                        held_first=held_first)

    return jax.jit(classifier_forward)


def stat_layout(cfg, n_held: int) -> Dict[str, slice]:
    """Where each count lies in the stats vector."""
    n = n_moe_layers(cfg) * n_held
    return {"token_slots": slice(0, 1), "valid_tokens": slice(1, 2),
            "valid_pairs": slice(2, 3), "expert_tokens": slice(3, 3 + n),
            "expert_valid_tokens": slice(3 + n, 3 + 2 * n)}


def unpack_stats(stats, cfg, n_held: int) -> Dict[str, np.ndarray]:
    """The stats vector as named counts; the expert counts shaped
    (MoE layers, n_held)."""
    stats = np.asarray(stats)
    out = {}
    for name, sl in stat_layout(cfg, n_held).items():
        v = stats[..., sl]
        out[name] = (v.reshape(stats.shape[:-1] + (n_moe_layers(cfg), n_held))
                     if name.startswith("expert") else v[..., 0])
    return out
