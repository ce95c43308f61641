"""Compile the serving path's device programs for a described TPU v5e.

Interpret mode runs a Pallas kernel's body on the CPU but checks none of
Mosaic's lowering rules, so a kernel can pass every parity test and still
be refused by the chip's compiler.  These tests hand the installed TPU
compiler a v5e that is described, not attached, and compile the fused
``cascade_score`` kernel in every variant the program calls (mask-only,
scores, compaction with and without ``compact_cols``; fp32 and int8
weights) at the served widths, with the ``block_m`` the autotuner picks
for that chip, plus the synthetic UDF's jitted forward.  Nothing runs:
a pass says the chip's compiler accepts the program, not that it is
fast or correct.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.proxy_family import (
    cascade_kernel_operands,
    pack_cascade,
    quantize_cascade,
)
from repro.kernels import autotune
from repro.kernels.proxy_score import cascade_score
from repro.training.proxy_models import LinearParams, MLPParams

V5E_KIND = "TPU v5 lite"
MAX_TILE = 8192  # the executor's tile: the largest block the scorer picks


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == V5E_KIND
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cascade_params(F: int, P: int):
    """P proxies over F features: all linear for P=2 (hidden bucket 2),
    alternating linear / 32-wide MLP for P=6 (hidden bucket 32)."""
    rng = np.random.RandomState(F + P)
    params = []
    for p in range(P):
        mean = rng.randn(F).astype(np.float32)
        scale = (np.abs(rng.randn(F)) + 0.5).astype(np.float32)
        if P == 2 or p % 2 == 0:
            params.append(LinearParams(w=rng.randn(F).astype(np.float32),
                                       b=np.float32(rng.randn()),
                                       mean=mean, scale=scale))
        else:
            params.append(MLPParams(w1=rng.randn(F, 32).astype(np.float32),
                                    b1=rng.randn(32).astype(np.float32),
                                    w2=rng.randn(32).astype(np.float32),
                                    b2=np.float32(rng.randn()),
                                    mean=mean, scale=scale))
    return params


VARIANTS = {
    # (with_scores, with_compaction, compact_cols)
    "masks": (False, False, None),           # engine submit: score_masks
    "scores": (True, False, None),           # score_margins, quant parity
    "compact_first": (False, True, (0,)),    # executor: score_compact
    "compact_all": (False, True, None),      # score_compact, every column
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("P", [2, 6])
@pytest.mark.parametrize("F", [64, 1024])
def test_cascade_score_compiles_for_v5e(one_chip, F, P, dtype, variant):
    packed = pack_cascade(_cascade_params(F, P))
    if dtype != "float32":
        packed = quantize_cascade(packed, dtype)
    w1, b1, w2, b2 = cascade_kernel_operands(packed)
    HP = w1.shape[1]
    block_m = autotune.choose_block_m(F, HP, P, dtype, max_tile=MAX_TILE,
                                      backend=V5E_KIND).block_m
    with_scores, with_compaction, compact_cols = VARIANTS[variant]

    def spec(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)
    args = (jax.ShapeDtypeStruct((MAX_TILE, F), jnp.float32,
                                 sharding=one_chip),
            spec(w1), spec(b1), spec(w2), spec(b2), vec,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            None if packed.out_scale is None else vec)

    def score(x, w1, b1, w2, b2, thr, n, out_scale):
        return cascade_score(
            x, w1, b1, w2, b2, thr, n, out_scale=out_scale, block_m=block_m,
            interpret=False, with_scores=with_scores,
            with_compaction=with_compaction, compact_cols=compact_cols)

    compiled = jax.jit(score).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_udf_forward_compiles_for_v5e(one_chip):
    """The synthetic UDF body at the library's default width (hidden
    256, depth 4) on a 1024-row batch."""
    from repro.data.synthetic import _train_udf_model

    rng = np.random.RandomState(0)
    x = rng.randn(64, 64).astype(np.float32)
    y = rng.randint(0, 4, 64)
    params, _predict, logits_fn = _train_udf_model(
        x, y, 4, hidden=256, depth=4, seed=0, steps=1)
    p_specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x_spec = jax.ShapeDtypeStruct((1024, 64), jnp.float32, sharding=one_chip)
    fwd = jax.jit(lambda p, xx: jnp.argmax(logits_fn(p, xx), axis=-1))
    compiled = fwd.lower(p_specs, x_spec).compile()
    assert compiled.memory_analysis() is not None


def test_moonlight_classifier_forward_compiles_for_v5e(one_chip):
    """The Moonlight-16B-A3B classifier UDF at its published widths (the
    chip's share: 8 held experts, a 20,480-id vocabulary slice, 5
    layers) on its smallest record bucket: 8 records of 256 tokens after
    a 128-column sketch.  Its weights fit with room (about 1.05 GB)."""
    from repro.configs.archs import MOONLIGHT_16B_A3B
    from repro.models import classifier

    cfg = MOONLIGHT_16B_A3B.replace(num_layers=5, vocab_size=20480)
    shapes = jax.eval_shape(lambda k: classifier.init(
        k, cfg, n_held=8, n_classes=4), jax.random.PRNGKey(0))
    p_specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    x_spec = jax.ShapeDtypeStruct((8, 384), jnp.float32, sharding=one_chip)
    fwd = classifier.row_forward(cfg, held_first=0, token_col=128)
    mem = fwd.lower(p_specs, x_spec).compile().memory_analysis()
    assert 1.0e9 < mem.argument_size_in_bytes < 1.1e9
