"""The dropless held-expert layer, the sequence classifier and the device
UDF, at a tiny width on the CPU, against the plain float32 reference of
the chip benchmark (``benchmarks/chip/reference_moonlight.py``) on
seeded weights."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig
from repro.core.query import DeviceUDF
from repro.models import classifier, moe
from repro.util import spans

ROOT = Path(__file__).resolve().parents[1]

# Moonlight's layout at a tiny width: one dense layer, then MoE layers
# with 16 routed experts (top-6, sigmoid, correction bias) and 2 shared
TINY = ModelConfig(
    name="tiny-moonlight", family="moe", num_layers=3, d_model=32, d_ff=16,
    vocab_size=64,
    attention=AttentionConfig(kind="mla", num_heads=2, num_kv_heads=2,
                              head_dim=8, rope_theta=50000.0, kv_lora_rank=8,
                              qk_nope_head_dim=8, qk_rope_head_dim=4,
                              v_head_dim=8),
    moe=MoEConfig(num_experts=16, top_k=6, expert_ff=16, num_shared=2,
                  first_dense=1, dense_ff=48, routed_scaling_factor=2.446),
    norm_eps=1e-5, dtype="float32")
HF = {  # the same model in the reference's (Hugging Face) keys
    "num_attention_heads": 2, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 8, "rope_theta": 50000.0,
    "rms_norm_eps": 1e-5, "num_experts_per_tok": 6, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid"}


@pytest.fixture(scope="module")
def ref():
    key = "chipbench_reference_moonlight"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, ROOT / "benchmarks" / "chip" / "reference_moonlight.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def _experts(n_held, seed=0, cfg=TINY):
    return jax.jit(lambda k: moe.init_held_experts(k, cfg, n_held))(
        jax.random.PRNGKey(seed))


def _tokens(n, s, seed=0):
    return np.random.RandomState(seed).randn(n, s).astype(np.float32)


def test_sigmoid_noaux_router_matches_reference(ref):
    p = _experts(16)
    x = jnp.asarray(_tokens(40, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        top_e, top_w = moe.route(moe.router_logits(p, x), p["bias"], TINY.moe)
        want_e, want_w = ref.route(
            jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p), HF, x)
    np.testing.assert_array_equal(np.asarray(top_e), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(top_w), np.asarray(want_w), rtol=1e-6)
    # the bias moves the choice, not the weights: they are the chosen
    # sigmoid scores, summing to the routed scale
    np.testing.assert_allclose(np.asarray(top_w).sum(1), 2.446, rtol=1e-5)
    scores = jax.nn.sigmoid(moe.router_logits(p, x))
    unbiased = np.asarray(jax.lax.top_k(scores, 6)[1])
    assert (np.sort(unbiased, 1) != np.sort(np.asarray(top_e), 1)).any()


def test_held_shares_add_up_to_the_uncut_layer(ref):
    """Eight ranks hold two experts each: their parts, with the shared
    experts counted once, give the layer that holds all sixteen, which is
    the reference's whole layer."""
    full = _experts(16, seed=1)
    x = jnp.asarray(_tokens(48, TINY.d_model, seed=1))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.held_experts_apply(full, TINY, x, 0)
        shared = moe.L.mlp_apply(full["shared"], TINY, x)
        parts = shared
        for r in range(8):
            share = dict(full, **{k: full[k][2 * r:2 * r + 2]
                                  for k in ("wg", "wi", "wo")})
            out, _ = moe.held_experts_apply(share, TINY, x, 2 * r)
            parts = parts + (out - shared)
        want = ref.experts(full, HF, x, 0)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_when_every_token_routes_to_one_expert(ref):
    p = _experts(4, seed=2)
    p["bias"] = p["bias"].at[5].set(100.0)   # every token chooses expert 5
    x = jnp.asarray(_tokens(300, TINY.d_model, seed=2))
    with jax.default_matmul_precision("highest"):
        out, top_e = moe.held_experts_apply(p, TINY, x, 4)
        want = ref.experts(p, HF, x, 4)
    load = np.asarray(moe.held_load(top_e, 4, 4))
    assert load[1] == 300 and load.sum() >= 300
    assert moe.expert_block(300, TINY.moe) < 300   # several blocks for it
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def tiny_model():
    p = jax.jit(lambda k: classifier.init(k, TINY, n_held=4, n_classes=4))(
        jax.random.PRNGKey(3))
    p["head_w"] = jax.random.normal(jax.random.PRNGKey(4), (TINY.d_model, 4))
    p["head_b"] = jnp.arange(4, dtype=jnp.float32) * 0.1
    return p


def _records(n, s=12, seed=0):
    """Right-padded token rows of lengths 1..s."""
    rng = np.random.RandomState(seed)
    tok = rng.randint(1, TINY.vocab_size, (n, s))
    lengths = rng.randint(1, s + 1, n)
    tok[np.arange(s)[None, :] >= lengths[:, None]] = classifier.PAD_ID
    return tok


def test_classifier_logits_match_reference(ref, tiny_model):
    tok = _records(7)
    with jax.default_matmul_precision("highest"):
        got, stats = classifier.classify(tiny_model, TINY, jnp.asarray(tok),
                                         held_first=4)
    want = ref.logits(tiny_model, HF, tok, first=4, block=3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    st = classifier.unpack_stats(stats, TINY, 4)
    lengths = (tok != 0).sum(1)
    assert st["token_slots"] == tok.size
    assert st["valid_tokens"] == lengths.sum()
    assert st["valid_pairs"] == (lengths * (lengths + 1) // 2).sum()
    assert st["expert_tokens"].shape == (2, 4)
    assert (st["expert_valid_tokens"] <= st["expert_tokens"]).all()


def test_a_records_logits_ignore_its_neighbours_and_its_bucket(tiny_model):
    fwd = classifier.row_forward(TINY, held_first=4, token_col=3)
    udf = DeviceUDF(fwd, tiny_model, buckets=(2, 8))
    rows = np.concatenate([np.zeros((9, 3), np.float32),
                           _records(9, seed=5).astype(np.float32)], axis=1)
    rows[:, :3] = 7.0    # columns the forward does not read
    with jax.default_matmul_precision("highest"):
        alone = np.concatenate([udf.logits(rows[i:i + 1]) for i in range(9)])
        together = udf.logits(rows)        # a full bucket and a 1-row piece
        shuffled = udf.logits(rows[::-1])[::-1]
    np.testing.assert_allclose(together, alone, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(shuffled, alone, rtol=1e-5, atol=1e-5)


def test_device_udf_pads_to_buckets_and_counts_every_call(tiny_model):
    fwd = classifier.row_forward(TINY, held_first=4, token_col=0)
    udf = DeviceUDF(fwd, tiny_model, buckets=(8, 2, 4))
    tok = _records(11, seed=6).astype(np.float32)
    spans.reset()
    spans.enable()
    try:
        labels = udf(tok)
    finally:
        spans.disable()
    names = spans.snapshot()
    spans.reset()
    assert {"udf.pad", "udf.dispatch", "udf.fetch"} <= set(names)
    assert udf.buckets == (2, 4, 8) and udf.bucket(3) == 4
    with pytest.raises(ValueError):
        udf.bucket(9)
    assert labels.shape == (11,) and labels.dtype.kind == "i"
    (t, n, slots, stats), = udf.counters
    assert n == 11 and slots == 8 + 4 and t > 0
    st = classifier.unpack_stats(stats, TINY, 4)
    assert st["token_slots"] == slots * tok.shape[1]
    assert st["valid_tokens"] == np.count_nonzero(tok)
    assert udf(np.zeros((0, tok.shape[1]), np.float32)).shape == (0,)
    assert len(udf.counters) == 1
