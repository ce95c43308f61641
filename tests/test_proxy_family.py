"""ProxyFamily registry + packed-parameter format invariants.

* registry lookup by name, alias, and params type;
* pack/unpack round-trip across families (property test): stacking a
  mixed cascade into the bucket-padded (F, H, P) tensors and slicing one
  stage back out reproduces the per-proxy packed form bit-for-bit;
* the linear +/- embedding is EXACT through the kernel (packed two-pass
  scores bit-identical to the affine reference);
* packed reference scoring agrees with each family's native scorer;
* the builder's classifier cache is keyed on family: a mixed builder
  trains per-predicate families and never reuses across kinds.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.proxy_family import (
    HIDDEN_BUCKETS,
    cascade_kernel_operands,
    family_of,
    get_family,
    hidden_bucket,
    pack_cascade,
    unpack_cascade,
)
from repro.kernels import ref
from repro.kernels.proxy_score import proxy_score
from repro.training.proxy_models import (
    LinearParams,
    MLPParams,
    packed_score,
)


def _linear(rng, F):
    return LinearParams(
        w=rng.randn(F).astype(np.float32),
        b=np.float32(rng.randn()),
        mean=rng.randn(F).astype(np.float32),
        scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32),
    )


def _mlp(rng, F, H):
    return MLPParams(
        w1=rng.randn(F, H).astype(np.float32),
        b1=rng.randn(H).astype(np.float32),
        w2=rng.randn(H).astype(np.float32),
        b2=np.float32(rng.randn()),
        mean=rng.randn(F).astype(np.float32),
        scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32),
    )


# ---------------------------------------------------------------- registry
def test_registry_lookup_and_aliases():
    assert get_family("linear") is get_family("svm")
    assert get_family("mlp1") is get_family("mlp")
    with pytest.raises(KeyError):
        get_family("tree")
    rng = np.random.RandomState(0)
    assert family_of(_linear(rng, 4)).name == "linear"
    assert family_of(_mlp(rng, 4, 3)).name == "mlp1"


def test_hidden_bucket_ladder():
    assert [hidden_bucket(h) for h in (1, 2, 3, 4, 5, 32, 33, 128)] == \
        [2, 2, 4, 4, 8, 32, 64, 128]
    assert hidden_bucket(129) == 256  # beyond the ladder: top-bucket multiples
    assert all(b2 == 2 * b1 for b1, b2 in zip(HIDDEN_BUCKETS, HIDDEN_BUCKETS[1:]))


# ----------------------------------------------------- pack/unpack roundtrip
@given(
    f=st.integers(3, 48),
    n_stages=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip_mixed_families(f, n_stages, seed):
    """unpack_cascade(pack_cascade(params), col) == family.pack(params[col])
    bit-for-bit, for any mix of families and hidden widths (bucket padding
    must be inert and reversible)."""
    rng = np.random.RandomState(seed)
    params = []
    for _ in range(n_stages):
        if rng.rand() < 0.5:
            params.append(_linear(rng, f))
        else:
            params.append(_mlp(rng, f, rng.randint(1, 40)))
    packed = pack_cascade(params)
    assert packed.H == hidden_bucket(max(packed.hidden))
    for col, p in enumerate(params):
        fam = family_of(p)
        direct = fam.pack(p)
        rt = unpack_cascade(packed, col)
        assert rt.hidden == direct.hidden
        np.testing.assert_array_equal(rt.w1, direct.w1)
        np.testing.assert_array_equal(rt.b1, direct.b1)
        np.testing.assert_array_equal(rt.w2, direct.w2)
        assert rt.b2 == direct.b2
        # the bucket-pad slots must be exactly zero (inert under relu)
        assert not packed.w1[:, direct.hidden:, col].any()
        assert not packed.w2[direct.hidden:, col].any()


@given(
    f=st.integers(3, 32),
    n=st.integers(1, 200),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=25, deadline=None)
def test_packed_score_matches_family_score(f, n, seed):
    """The folded packed form scores within float tolerance of each
    family's native (standardize-then-score) path."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    for params in (_linear(rng, f), _mlp(rng, f, rng.randint(1, 20))):
        fam = family_of(params)
        native = np.asarray(fam.score(params, x))
        folded = packed_score(fam.pack(params), x)
        np.testing.assert_allclose(folded, native, rtol=1e-4, atol=1e-4)


def test_linear_embedding_exact_through_kernel():
    """The +/- trick is exact: the two-pass packed kernel's scores are
    bit-identical to the single affine reference for a linear stack."""
    rng = np.random.RandomState(3)
    F, N, P = 24, 300, 4
    x = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F, P).astype(np.float32)
    b = rng.randn(P).astype(np.float32)
    thr = rng.randn(P).astype(np.float32)
    s, m = proxy_score(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                       jnp.asarray(thr), interpret=True)
    sref, mref = ref.proxy_score_ref(jnp.asarray(x), jnp.asarray(w),
                                     jnp.asarray(b), jnp.asarray(thr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sref))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mref))


def test_cascade_kernel_operands_layout():
    """h-major flattening: column h*P + p of w1 is hidden unit h of stage
    p, and w2 is the matching block-diagonal readout."""
    rng = np.random.RandomState(5)
    params = [_linear(rng, 6), _mlp(rng, 6, 3)]
    packed = pack_cascade(params)
    w1, b1, w2, b2 = cascade_kernel_operands(packed)
    H, P = packed.H, packed.n_stages
    assert w1.shape == (6, H * P) and w2.shape == (H * P, P)
    for h in range(H):
        for p in range(P):
            np.testing.assert_array_equal(w1[:, h * P + p], packed.w1[:, h, p])
            assert b1[h * P + p] == packed.b1[h, p]
            # readout row touches exactly its own stage's column
            expect = np.zeros(P, np.float32)
            expect[p] = packed.w2[h, p]
            np.testing.assert_array_equal(w2[h * P + p], expect)


# ----------------------------------------------------- builder family keying
def test_builder_mixed_assigns_families_and_keys_cache():
    from repro.core.builder import ProxyBuilder
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=3000, correlation=0.85, seed=11)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=800, seed=11,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=12)
    b = ProxyBuilder(q, ds.x[:800], kind="mixed")
    assert b.family_for(0) == "linear" and b.family_for(1) == "mlp1"
    p0, _ = b.get_proxy(0, ())
    p1, _ = b.get_proxy(1, ())
    assert p0.family == "linear" and p1.family == "mlp1"
    # cache keys carry the family; same (pred, prefix) under another family
    # is a MISS, not a cross-family reuse
    assert (0, frozenset(), "linear") in b._proxies
    b2 = ProxyBuilder(q, ds.x[:800], kind="mlp")
    b2._proxies = dict(b._proxies)  # transplant, as rebase does
    q0, _ = b2.get_proxy(0, ())
    assert q0.family == "mlp1"
    assert b2.stats.n_reused == 0
    # per-predicate family map (how reoptimize pins an incumbent plan's
    # exact assignment, parity rule or not)
    b3 = ProxyBuilder(q, ds.x[:800], kind={0: "mlp1", 1: "linear"})
    assert b3.family_for(0) == "mlp1" and b3.family_for(1) == "linear"


# --------------------------------------------------- column-range family
def test_column_range_family_equals_an_svm_on_the_prefix_alone():
    """``svm[0:16]`` over 40 columns trains and scores on the first 16
    alone: the same masks as the linear family given those columns only;
    its params are widened so that every other column adds an exact 0,
    through the packed form and the float64 proxy reference alike."""
    from repro.core.proxy import train_proxy
    from repro.core.proxy_family import LINEAR, column_range

    rng = np.random.RandomState(7)
    x = rng.randn(600, 40).astype(np.float32)
    x[:, 16:] = rng.randint(1, 20000, (600, 24))   # token ids, never read
    y = (x[:, :16] @ rng.randn(16) + 0.3 * rng.randn(600)) > 0
    fam = get_family("svm[0:16]")
    assert fam is get_family("linear[0:16]") is column_range("linear", 0, 16)
    assert fam.name == "linear[0:16]" and family_of(
        fam.train(x, np.where(y, 1.0, -1.0), 0)) is LINEAR
    wide = train_proxy(x, y, 0, (), kind="svm[0:16]", seed=3)
    narrow = train_proxy(x[:, :16], y, 0, (), kind="svm", seed=3)
    assert wide.family == "linear[0:16]"
    for alpha in (0.8, 0.9, 0.95):
        np.testing.assert_array_equal(wide.mask(x, alpha),
                                      narrow.mask(x[:, :16], alpha))
    p = wide.params
    assert p.w.shape == (40,) and not p.w[16:].any()
    assert not p.mean[16:].any() and (p.scale[16:] == 1).all()
    np.testing.assert_array_equal(p.w[:16], np.asarray(narrow.params.w))
    z = (x.astype(np.float64) - p.mean) / p.scale
    np.testing.assert_array_equal(
        z @ p.w.astype(np.float64),
        z[:, :16] @ np.asarray(narrow.params.w, np.float64))
    packed = fam.pack(p)
    assert not packed.w1[16:].any()
    with pytest.raises(KeyError):
        get_family("mlp[0:16]")
