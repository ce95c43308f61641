"""Mixture-of-Experts decoder family (qwen3-moe, deepseek-v2-lite).

Token dispatch uses the argsort-capacity scheme (static shapes, no one-hot
(tokens x experts x capacity) blow-up): tokens are sorted by assigned expert,
each expert processes a fixed-capacity (E, C, D) buffer, overflow tokens fall
back to zero contribution (standard dropping MoE; capacity_factor controls
the drop rate).  Under expert-parallel sharding the (E, C, D) buffer is
sharded on E — XLA materializes the all-to-all from the resharding.

DeepSeek-V2-Lite layers use MLA attention + (2 shared + 64 routed top-6)
experts with the first layer dense; qwen3 uses GQA(+qk-norm) + 128 routed
top-8.

``held_experts_apply`` is the dropless layer of one expert-parallel rank:
it holds a contiguous range of the routed experts, routes every token over
all of them (``route``: DeepSeek-V3's sigmoid router with its correction
bias), and computes its own experts' part of the result for every token
routed to them, in static-size blocks of a token list sorted by expert, so
no token is dropped under any imbalance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed import ctx
from repro.models import layers as L
from repro.models import mla as MLA


# ------------------------------------------------------------ expert layer
def init_experts(key, cfg):
    m = cfg.moe
    d, f = cfg.d_model, m.expert_ff
    dt = L.param_dtype(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, m.num_experts), dtype=jnp.float32),
        "wg": L.dense_init(ks[1], (m.num_experts, d, f), dtype=dt),
        "wi": L.dense_init(ks[2], (m.num_experts, d, f), dtype=dt),
        "wo": L.dense_init(ks[3], (m.num_experts, f, d), in_axis=-2, dtype=dt),
    }
    if m.num_shared:
        p["shared"] = L.init_mlp(ks[4], cfg, d_ff=m.num_shared * m.expert_ff)
    return p


def moe_capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(m.top_k * n_tokens / m.num_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_apply(p, cfg, x):
    """x: (B, S, D) -> (out, aux_loss)."""
    m = cfg.moe
    B, S, D = x.shape
    N = B * S
    xt = x.reshape(N, D)
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, m.top_k)  # (N, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32), axis=1), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * m.num_experts * m.router_aux_weight

    # ---- argsort-capacity dispatch
    C = moe_capacity(cfg, N)
    flat_e = top_e.reshape(-1)  # (N*K,)
    sort_idx = jnp.argsort(flat_e)  # (N*K,)
    sorted_e = flat_e[sort_idx]
    # rank within expert group
    group_start = jnp.searchsorted(sorted_e, jnp.arange(m.num_experts))
    rank = jnp.arange(N * m.top_k) - group_start[sorted_e]
    dest = jnp.where(rank < C, sorted_e * C + rank, m.num_experts * C)  # trash row
    src_token = sort_idx // m.top_k
    buf = jnp.zeros((m.num_experts * C + 1, D), xt.dtype)
    buf = buf.at[dest].set(xt[src_token], mode="drop")
    buf = buf[:-1].reshape(m.num_experts, C, D)

    # ---- per-expert gated MLP
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    eout = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(m.num_experts * C, D)
    eout = jnp.concatenate([eout, jnp.zeros((1, D), eout.dtype)], axis=0)

    # ---- combine: weighted scatter-add back to tokens
    gathered = eout[dest]  # (N*K, D) in sorted order
    weights = top_p.reshape(-1)[sort_idx].astype(gathered.dtype)  # (N*K,)
    out = jnp.zeros((N, D), xt.dtype).at[src_token].add(gathered * weights[:, None])

    if m.num_shared:
        out = out + L.mlp_apply(p["shared"], cfg, xt)
    return out.reshape(B, S, D), aux


# ------------------------------------------------- expert-parallel shard_map
def moe_apply_ep(p, cfg, x):
    """Expert-parallel MoE via shard_map (§Perf opt variant).

    Under TP the token activations are replicated across the "model" axis,
    so dispatch needs NO collectives: each model-rank selects the tokens
    routed to ITS expert block locally, runs the expert FFN, scatter-adds a
    partial output, and a single psum over "model" combines the top-k
    contributions.  Replaces the GSPMD-chosen (N*K, D) all-reduce/all-gather
    (3.3 TB/device/layer on qwen3 x prefill_32k) with one (N_local, D) psum.
    """
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from repro.distributed import ctx

    mesh = ctx.get_mesh()
    m = cfg.moe
    if mesh is None or "model" not in mesh.axis_names or m.num_experts % mesh.shape["model"]:
        return moe_apply(p, cfg, x)
    tp = mesh.shape["model"]
    e_local = m.num_experts // tp
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    b_spec = batch_axes if x.shape[0] % int(
        __import__("numpy").prod([mesh.shape[a] for a in batch_axes])
    ) == 0 else None

    def local_fn(xl, router, wg, wi, wo):
        Bl, S, D = xl.shape
        N = Bl * S
        xt = xl.reshape(N, D)
        logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        density = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32), axis=1), axis=0
        )
        aux = jnp.sum(density * jnp.mean(probs, axis=0)) * m.num_experts * m.router_aux_weight

        midx = lax.axis_index("model")
        flat_e = top_e.reshape(-1)
        mine = (flat_e // e_local) == midx  # assignments routed to MY experts
        local_e = jnp.where(mine, flat_e - midx * e_local, e_local)  # e_local = trash
        C = moe_capacity(cfg, N)
        sort_idx = jnp.argsort(local_e)
        sorted_e = local_e[sort_idx]
        group_start = jnp.searchsorted(sorted_e, jnp.arange(e_local))
        rank = jnp.arange(N * m.top_k) - group_start[jnp.minimum(sorted_e, e_local - 1)]
        valid = (sorted_e < e_local) & (rank < C)
        slot = jnp.where(valid, sorted_e * C + rank, e_local * C)
        src_token = sort_idx // m.top_k
        # build slot -> token map, then gather tokens DIRECTLY into the buffer
        slot_token = jnp.full((e_local * C + 1,), N, jnp.int32)
        slot_token = slot_token.at[slot].set(src_token.astype(jnp.int32), mode="drop")
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        buf = xt_pad[slot_token[:-1]].reshape(e_local, C, D)

        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum("ecd,edf->ecf", buf, wi)
        eout = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e_local * C, D)
        eout = jnp.concatenate([eout, jnp.zeros((1, D), eout.dtype)], axis=0)

        gathered = eout[slot]  # sorted-assignment order; trash slot -> zeros
        weights = top_p.reshape(-1)[sort_idx].astype(gathered.dtype)
        out = jnp.zeros((N, D), xt.dtype).at[src_token].add(
            gathered * (weights * valid.astype(gathered.dtype))[:, None]
        )
        out = lax.psum(out, "model")
        return out.reshape(Bl, S, D), lax.pmean(aux, "model")

    out, aux = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(b_spec, None, None),
            P(),  # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(b_spec, None, None), P()),
        check_rep=False,
    )(x, p["router"], p["wg"], p["wi"], p["wo"])
    if m.num_shared:
        out = out + L.mlp_apply(p["shared"], cfg, x.reshape(-1, x.shape[-1])).reshape(x.shape)
    return out, aux


# ------------------------------------------------- held experts (dropless)
def route(logits, bias, moe_cfg):
    """DeepSeek-V3's top-k choice over all experts (``noaux_tc``, one
    group) from float32 router logits (N, E) and the correction bias (E,).

    Returns ``(top_e (N, K) int32, top_w (N, K) float32)``: the scores are
    the sigmoid of the logits; the choice ranks ``scores + bias``; the
    weights are the chosen experts' scores, renormalized over the K chosen
    and multiplied by ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(logits)
    _, top_e = lax.top_k(scores + bias, moe_cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_w * moe_cfg.routed_scaling_factor


def init_held_experts(key, cfg, n_held: int):
    """Router over all ``num_experts`` with its correction bias (seeded
    small and nonzero), the weights of ``n_held`` routed experts, and the
    shared experts as one MLP."""
    m = cfg.moe
    d, f = cfg.d_model, m.expert_ff
    dt = L.param_dtype(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "router": L.dense_init(ks[0], (d, m.num_experts), dtype=dt),
        "wg": L.dense_init(ks[1], (n_held, d, f), dtype=dt),
        "wi": L.dense_init(ks[2], (n_held, d, f), dtype=dt),
        "wo": L.dense_init(ks[3], (n_held, f, d), in_axis=-2, dtype=dt),
        "bias": 0.02 * jax.random.normal(ks[4], (m.num_experts,), jnp.float32),
    }
    if m.num_shared:
        p["shared"] = L.init_mlp(ks[5], cfg, d_ff=m.num_shared * m.expert_ff)
    return p


def router_logits(p, x):
    """float32 router logits: the activations and weights upcast and
    multiplied at full float32 precision, as DeepSeek-V3's gate does."""
    return jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def expert_block(n_tokens: int, moe_cfg) -> int:
    """Tokens per block of the held-expert loop: about a third of an
    expert's mean load, a power of two in [128, 1024]."""
    mean = n_tokens * moe_cfg.top_k / moe_cfg.num_experts
    b = 128
    while b < 1024 and 3 * b < mean:
        b *= 2
    return b


def held_load(top_e, first: int, n_held: int, mask=None):
    """Assignments (token, chosen expert) per held expert, (n_held,) int32;
    with ``mask`` (N,) only those of the masked tokens."""
    hit = top_e[..., None] == (first + jnp.arange(n_held))  # (N, K, n)
    if mask is not None:
        hit = hit & mask[:, None, None]
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


def held_experts_apply(p, cfg, x, first: int):
    """Dropless expert layer of the rank holding routed experts
    ``[first, first + n_held)``; x (N, D) -> (out (N, D), top_e (N, K)).

    Routes over all experts, then sorts the (token, expert) assignments by
    held expert (assignments to experts held elsewhere sort last and are
    not computed).  Each held expert runs its gated MLP over its own tokens
    in blocks of ``expert_block`` rows, as many blocks as its load needs,
    and adds them weighted into the output: static shapes, no capacity, no
    token dropped.  The shared experts run on every token.  Expert outputs
    accumulate in float32; ``out`` is in ``x``'s dtype."""
    m = cfg.moe
    n_held = p["wg"].shape[0]
    N, D = x.shape
    K = m.top_k
    top_e, top_w = route(router_logits(p, x), p["bias"], m)
    local = top_e - first
    mine = (local >= 0) & (local < n_held)
    flat_e = jnp.where(mine, local, n_held).reshape(-1)  # (N*K,)
    flat_w = jnp.where(mine, top_w, 0.0).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    block = expert_block(N, m)
    pad = jnp.zeros((block,), jnp.int32)
    tok = jnp.concatenate([(order // K).astype(jnp.int32), pad])
    wgt = jnp.concatenate([flat_w[order], pad.astype(jnp.float32)])
    load = jnp.bincount(flat_e, length=n_held + 1)[:n_held].astype(jnp.int32)
    start = jnp.cumsum(load) - load
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    out = jnp.zeros((N, D), jnp.float32)
    for e in range(n_held):
        def body(b, acc, e=e):
            s = start[e] + b * block
            t = lax.dynamic_slice(tok, (s,), (block,))
            w = lax.dynamic_slice(wgt, (s,), (block,))
            w = jnp.where(b * block + jnp.arange(block) < load[e], w, 0.0)
            xb = x[t]
            h = act(xb @ p["wg"][e]) * (xb @ p["wi"][e])
            return acc.at[t].add((h @ p["wo"][e]).astype(jnp.float32)
                                 * w[:, None])
        out = lax.fori_loop(0, (load[e] + block - 1) // block, body, out)
    if m.num_shared:
        out = out + L.mlp_apply(p["shared"], cfg, x).astype(jnp.float32)
    return out.astype(x.dtype), top_e


def _moe_dispatch(p, cfg, x):
    from repro.distributed import ctx

    if ctx.ep_enabled():
        return moe_apply_ep(p, cfg, x)
    return moe_apply(p, cfg, x)


# --------------------------------------------------------------- families
def _is_mla(cfg) -> bool:
    return cfg.attention.kind == "mla"


def init_moe_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    attn = MLA.init_mla(k1, cfg) if _is_mla(cfg) else L.init_gqa(k1, cfg)
    return {
        "ln1": L.init_rms_for(cfg, cfg.d_model),
        "attn": attn,
        "ln2": L.init_rms_for(cfg, cfg.d_model),
        "experts": init_experts(k2, cfg),
    }


def init_dense_layer(key, cfg):
    """Leading dense layers (deepseek-v2-lite layer 0)."""
    k1, k2 = jax.random.split(key)
    attn = MLA.init_mla(k1, cfg) if _is_mla(cfg) else L.init_gqa(k1, cfg)
    return {
        "ln1": L.init_rms_for(cfg, cfg.d_model),
        "attn": attn,
        "ln2": L.init_rms_for(cfg, cfg.d_model),
        "mlp": L.init_mlp(k2, cfg, d_ff=cfg.moe.dense_ff),
    }


def init(key, cfg):
    m = cfg.moe
    k_emb, k_dense, k_layers = jax.random.split(key, 3)
    params = L.init_embed(k_emb, cfg)
    if m.first_dense:
        params["dense_layers"] = L.stack_init(
            lambda k: init_dense_layer(k, cfg), k_dense, m.first_dense
        )
    params["layers"] = L.stack_init(
        lambda k: init_moe_layer(k, cfg), k_layers, cfg.num_layers - m.first_dense
    )
    params["final_norm"] = L.init_rms_for(cfg, cfg.d_model)
    return params


def _attend(lp, cfg, h, positions):
    if _is_mla(cfg):
        return MLA.mla_attend(lp["attn"], cfg, h, positions)
    return L.gqa_attend(lp["attn"], cfg, h, positions, causal=True)


def backbone(params, cfg, x, positions):
    m = cfg.moe
    aux_total = jnp.zeros((), jnp.float32)

    if m.first_dense:

        def dense_body(h, lp):
            hn = L.apply_norm(cfg, h, lp["ln1"])
            h = h + _attend(lp, cfg, hn, positions)
            hn = L.apply_norm(cfg, h, lp["ln2"])
            return h + L.mlp_apply(lp["mlp"], cfg, hn)

        x = L.scan_layers(dense_body, x, params["dense_layers"], remat=cfg.remat)

    def moe_body(carry, lp):
        h, aux = carry
        hn = L.apply_norm(cfg, h, lp["ln1"])
        h = h + _attend(lp, cfg, hn, positions)
        hn = L.apply_norm(cfg, h, lp["ln2"])
        mo, a = _moe_dispatch(lp["experts"], cfg, hn)
        return (ctx.constrain_tokens(h + mo), aux + a), None

    body = jax.checkpoint(moe_body) if cfg.remat else moe_body
    (x, aux_total), _ = lax.scan(lambda c, lp: body(c, lp), (x, aux_total), params["layers"])
    return L.apply_norm(cfg, x, params["final_norm"]), aux_total


def forward(params, cfg, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(params, cfg, tokens)
    x, _aux = backbone(params, cfg, x, positions)
    return L.lm_logits(params, cfg, x)


def loss(params, cfg, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(params, cfg, tokens)
    x, aux = backbone(params, cfg, x, positions)
    logits = L.lm_logits(params, cfg, x)
    ce = L.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return ce + aux, {"aux": aux, "ce": ce}


# --------------------------------------------------------------- serving
def init_cache(cfg, batch: int, max_len: int):
    a = cfg.attention
    dtype = L.param_dtype(cfg)
    m = cfg.moe
    n_moe = cfg.num_layers - m.first_dense
    if _is_mla(cfg):
        cache = {
            "ckv": jnp.zeros((n_moe, batch, max_len, a.kv_lora_rank), dtype),
            "krope": jnp.zeros((n_moe, batch, max_len, a.qk_rope_head_dim), dtype),
        }
        if m.first_dense:
            cache["dense_ckv"] = jnp.zeros((m.first_dense, batch, max_len, a.kv_lora_rank), dtype)
            cache["dense_krope"] = jnp.zeros(
                (m.first_dense, batch, max_len, a.qk_rope_head_dim), dtype
            )
    else:
        shape = (n_moe, batch, max_len, a.num_kv_heads, a.head_dim)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if m.first_dense:
            cache["dense_k"] = jnp.zeros((m.first_dense,) + shape[1:], dtype)
            cache["dense_v"] = jnp.zeros((m.first_dense,) + shape[1:], dtype)
    cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


def prefill(params, cfg, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    m = cfg.moe
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(params, cfg, tokens)
    cache = {"pos": jnp.asarray(S, jnp.int32)}

    def attn_prefill(lp, h):
        if _is_mla(cfg):
            out, ckv, krope = MLA.mla_prefill(lp["attn"], cfg, h, positions)
            return out, (ckv, krope)
        a = cfg.attention
        q, k, v = L.gqa_project_qkv(lp["attn"], cfg, h)
        q = L.apply_rope(q, positions, a.rope_theta)
        k = L.apply_rope(k, positions, a.rope_theta)
        out = L.mha(q, k, v, causal=True, q_positions=positions, kv_positions=positions)
        return out.reshape(B, S, -1) @ lp["attn"]["wo"], (k, v)

    if m.first_dense:

        def dense_body(h, lp):
            hn = L.apply_norm(cfg, h, lp["ln1"])
            out, kv = attn_prefill(lp, hn)
            h = h + out
            hn = L.apply_norm(cfg, h, lp["ln2"])
            return ctx.constrain_tokens(h + L.mlp_apply(lp["mlp"], cfg, hn)), kv

        x, dkv = lax.scan(dense_body, x, params["dense_layers"])
        if _is_mla(cfg):
            cache["dense_ckv"], cache["dense_krope"] = dkv
        else:
            cache["dense_k"], cache["dense_v"] = dkv

    def moe_body(h, lp):
        hn = L.apply_norm(cfg, h, lp["ln1"])
        out, kv = attn_prefill(lp, hn)
        h = h + out
        hn = L.apply_norm(cfg, h, lp["ln2"])
        mo, _aux = _moe_dispatch(lp["experts"], cfg, hn)
        return ctx.constrain_tokens(h + mo), kv

    x, kv = lax.scan(moe_body, x, params["layers"])
    if _is_mla(cfg):
        cache["ckv"], cache["krope"] = kv
    else:
        cache["k"], cache["v"] = kv
    x = L.apply_norm(cfg, x, params["final_norm"])
    return L.lm_logits(params, cfg, x[:, -1:, :])[:, 0], cache


def decode_step(params, cfg, cache, tokens):
    B = tokens.shape[0]
    m = cfg.moe
    pos = cache["pos"]
    x = L.embed_tokens(params, cfg, tokens[:, None])
    new_cache = {"pos": pos + 1}

    def attn_decode(lp, h, entry):
        if _is_mla(cfg):
            ckv, krope = entry
            out, ckv, krope = MLA.mla_decode(lp["attn"], cfg, h, ckv, krope, pos)
            return out, (ckv, krope)
        ck, cv = entry
        out, ck, cv = L.gqa_decode(lp["attn"], cfg, h, ck, cv, pos)
        return out, (ck, cv)

    if m.first_dense:

        def dense_body(h, xs):
            lp, *entry = xs
            hn = L.apply_norm(cfg, h, lp["ln1"])
            out, entry = attn_decode(lp, hn, tuple(entry))
            h = h + out
            hn = L.apply_norm(cfg, h, lp["ln2"])
            return ctx.constrain_tokens(h + L.mlp_apply(lp["mlp"], cfg, hn)), entry

        dkeys = ("dense_ckv", "dense_krope") if _is_mla(cfg) else ("dense_k", "dense_v")
        x, dkv = lax.scan(dense_body, x, (params["dense_layers"], cache[dkeys[0]], cache[dkeys[1]]))
        new_cache[dkeys[0]], new_cache[dkeys[1]] = dkv

    def moe_body(h, xs):
        lp, *entry = xs
        hn = L.apply_norm(cfg, h, lp["ln1"])
        out, entry = attn_decode(lp, hn, tuple(entry))
        h = h + out
        hn = L.apply_norm(cfg, h, lp["ln2"])
        mo, _aux = _moe_dispatch(lp["experts"], cfg, hn)
        return ctx.constrain_tokens(h + mo), entry

    keys = ("ckv", "krope") if _is_mla(cfg) else ("k", "v")
    x, kv = lax.scan(moe_body, x, (params["layers"], cache[keys[0]], cache[keys[1]]))
    new_cache[keys[0]], new_cache[keys[1]] = kv
    x = L.apply_norm(cfg, x, params["final_norm"])
    return L.lm_logits(params, cfg, x)[:, 0], new_cache
