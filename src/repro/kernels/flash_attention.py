"""Blockwise (flash) causal attention kernel for prefill, with GQA.

Online-softmax over KV blocks: running row-max and row-sum live in VMEM
scratch; the (Sq, Sk) score matrix is never materialized in HBM.  Block
shapes are (block_q, D) x (block_k, D) with D the head dim (128/256 —
MXU-aligned).  Grid: (batch*q_heads, Sq / block_q); the kv-block loop is a
``lax.fori_loop`` inside the kernel, bounded by the causal frontier.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, sk, scale, causal):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale  # (block_q, D)
    D = q.shape[-1]

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, D), jnp.float32)

    q_start = qi * block_q
    n_kv = sk // block_k
    if causal:
        # only kv blocks whose start <= last q position
        n_kv = jnp.minimum(n_kv, (q_start + block_q + block_k - 1) // block_k)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.astype(jnp.float32).T, preferred_element_type=jnp.float32)
        if causal:
            qpos = q_start + jnp.arange(block_q)
            kpos = j * block_k + jnp.arange(block_k)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "scale")
)
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128, interpret: bool = True):
    """q: (B, Sq, H, D); k/v: (B, Sk, K, D), H % K == 0.  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)

    # layout: fold batch and heads into the grid's leading dim
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, D)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, D)

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, sk=Sk, scale=scale, causal=causal
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
