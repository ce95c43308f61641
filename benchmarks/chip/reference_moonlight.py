"""Plain reference of the ``moonlight`` UDF family: a float32 forward of
the cut Moonlight-16B-A3B classifier, and ``reference.py``'s proxy
reference unchanged.

Imports nothing of the program.  The forward follows the published
architecture (DeepSeek-V3 layout, the config's keys by their Hugging Face
names) in straightforward ``jax.numpy``: embedding; per layer RMSNorm,
causal multi-head latent attention (no q compression; the joint KV
down-projection, its RMSNorm, the per-head up-projection; RoPE on the
64 rope dims of q and the shared k; softmax scale ``1/sqrt(qk_nope +
qk_rope)``), residual, RMSNorm, then the dense gated MLP (layer 0) or
the experts; final RMSNorm; mean over the valid tokens (id != 0); the
head.  The router takes float32 logits, sigmoid scores, picks the top
``num_experts_per_tok`` of ``scores + e_score_correction_bias`` (one
group), and weights the chosen experts by their scores renormalized over
the chosen and times ``routed_scaling_factor``.  The chip's share, as
the program computes it: every held expert runs on every token and is
weighted by its routing weight (0 where the token did not choose it);
experts held elsewhere add nothing; the shared experts run on every
token.

Departures from the published model, each the program's too: RoPE
rotates the two halves of the rope dims (the checkpoint's interleaved
pairs are a fixed permutation of those weight columns, and the weights
here are random); the vocabulary, the held experts and the depth are the
configuration's cut; the output is a classifier head, not the LM head.

Every matmul runs under ``jax.default_matmul_precision("highest")`` on
float32 copies of the served weights, made one layer at a time (no
second copy of the model is held), over blocks of records.
"""
from __future__ import annotations

import importlib.util
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    key = "chipbench_" + name
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(__file__).resolve().parent / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


_base = _sibling("reference")
orig_pass = _base.orig_pass
proxy_scores = _base.proxy_scores
stated_scores = _base.stated_scores
stated_flips = _base.stated_flips
decision_gaps = _base.decision_gaps

BLOCK_RECORDS = 16
PAD_ID = 0


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, H, d): rotate the pair (i, i + d/2) by position * theta^(-2i/d)."""
    S, d = x.shape[1], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(S, dtype=np.float32)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, cfg, x):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    q = (x @ p["wq"]).reshape(B, S, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], cfg["rope_theta"])
    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rope(kv_a[..., None, rank:], cfg["rope_theta"])  # (B, S, 1, rd)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope[:, :, 0]))
    scores = scores / np.sqrt(nope + rd)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, H * vd)
    return out @ p["wo"]


def mlp(p, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def route(p, cfg, x):
    """(top_e, top_w) of every token of x (N, D)."""
    scores = jax.nn.sigmoid(x @ p["router"])
    k = cfg["num_experts_per_tok"]
    _, top_e = jax.lax.top_k(scores + p["bias"], k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"] and k > 1:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_w * cfg["routed_scaling_factor"]


def experts(p, cfg, x, first):
    """The chip's share of one expert layer over x (N, D): its held
    experts on every token, weighted by the routing, plus the shared
    experts."""
    top_e, top_w = route(p, cfg, x)
    out = mlp(p["shared"], x) if "shared" in p else jnp.zeros_like(x)
    for e in range(p["wg"].shape[0]):
        w = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), axis=-1)
        out = out + w[:, None] * mlp(
            {"wg": p["wg"][e], "wi": p["wi"][e], "wo": p["wo"][e]}, x)
    return out


@partial(jax.jit, static_argnames=("cfg_items", "first", "dense"))
def _layer(lp, h, *, cfg_items, first, dense):
    cfg = dict(cfg_items)
    lp = _f32(lp)
    eps = cfg["rms_norm_eps"]
    h = h + attention(lp["attn"], cfg, rms_norm(h, lp["ln1"], eps))
    hn = rms_norm(h, lp["ln2"], eps)
    if dense:
        return h + mlp(lp["mlp"], hn)
    B, S, D = hn.shape
    return h + experts(lp["experts"], cfg, hn.reshape(B * S, D),
                       first).reshape(B, S, D)


def _layers(stack):
    n = jax.tree.leaves(stack)[0].shape[0]
    return [jax.tree.map(lambda a, i=i: a[i], stack) for i in range(n)]


def hidden_of(params, cfg: dict, tokens, *, first: int):
    """Final-normed float32 hidden states (B, S, D) of tokens (B, S)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
        for lp in _layers(params.get("dense_layers", {})) if "dense_layers" in params else []:
            h = _layer(lp, h, cfg_items=items, first=first, dense=True)
        for lp in _layers(params["layers"]):
            h = _layer(lp, h, cfg_items=items, first=first, dense=False)
        return rms_norm(h, jnp.asarray(params["final_norm"], jnp.float32),
                        cfg["rms_norm_eps"])


def logits(params, cfg: dict, tokens: np.ndarray, *, first: int,
           block: int = BLOCK_RECORDS) -> np.ndarray:
    """float64 logits (N, C) of token rows (N, S), in blocks of records."""
    tokens = np.asarray(tokens, np.int32)
    w = np.asarray(params["head_w"], np.float64)
    b = np.asarray(params["head_b"], np.float64)
    out = []
    for s in range(0, len(tokens), block):
        t = tokens[s:s + block]
        h = np.asarray(hidden_of(params, cfg, t, first=first), np.float64)
        valid = (t != PAD_ID)[..., None]
        pool = (h * valid).sum(axis=1) / np.maximum(valid.sum(axis=1), 1)
        out.append(pool @ w + b)
    return np.concatenate(out)
