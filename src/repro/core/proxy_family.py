"""ProxyFamily registry and the packed device-resident parameter format.

This module replaces the stringly-typed ``kind: "svm" | "mlp"`` dispatch
that used to be scattered across the proxy stack.  A **family** owns
everything the system needs to know about one class of proxy scorer:

* how to **train** it (``train(x, y, seed)`` -> params),
* how to **score** with raw params (the reference path — used by the
  optimizer on the tiny optimization sample and by ``kernels/ref.py``
  parity oracles),
* how to **pack** params into the folded depth-1 MLP form
  (``training.proxy_models.PackedProxy``) the fused cascade kernel
  executes: ``score(x) = relu(x @ w1 + b1) @ w2 + b2`` with the feature
  standardizer folded in once at pack time.

Because linear models embed exactly (``relu(z) - relu(-z) == z``,
bit-for-bit), one packed format — and therefore ONE fused Pallas scorer —
covers every registered family; there is no per-kind execution branch left
anywhere downstream of this module.

``pack_cascade`` stacks the per-stage packed proxies of a whole plan into
bucket-padded ``(F, H, P)`` tensors (H = the hidden-width bucket, P = the
number of stages); ``unpack_cascade`` is its exact inverse per stage, and
is property-tested round-trip in ``tests/test_proxy_family.py``.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple, Type

import jax
import numpy as np

from repro.training import proxy_models as pm
from repro.training.proxy_models import PackedProxy


@dataclass(frozen=True)
class ProxyFamily:
    """One registered proxy-model family (linear SVM, depth-1 MLP, ...)."""

    name: str
    params_cls: Type
    train: Callable[[np.ndarray, np.ndarray, int], object]  # (x, y∈{-1,1}, seed)
    score: Callable[[object, np.ndarray], np.ndarray]  # reference scorer
    pack: Callable[[object], PackedProxy]  # fold standardizer + lower to packed

    def __repr__(self) -> str:  # keep plan dumps readable
        return f"ProxyFamily({self.name!r})"


_REGISTRY: Dict[str, ProxyFamily] = {}
_BY_PARAMS: Dict[Type, ProxyFamily] = {}
_ALIASES = {"svm": "linear", "mlp": "mlp1"}


def register_family(family: ProxyFamily, *, aliases: Sequence[str] = ()) -> ProxyFamily:
    _REGISTRY[family.name] = family
    _BY_PARAMS[family.params_cls] = family
    for a in aliases:
        _ALIASES[a] = family.name
    return family


_COLUMN_RANGE = re.compile(r"^(\w+)\[(\d+):(\d+)\]$")


def get_family(name: str) -> ProxyFamily:
    """Resolve a family by canonical name or legacy alias ("svm", "mlp"),
    or a column-range family by ``<name>[lo:hi]`` (``column_range``)."""
    key = _ALIASES.get(name, name)
    if key not in _REGISTRY:
        m = _COLUMN_RANGE.match(name)
        if m is not None:
            return column_range(get_family(m.group(1)).name,
                                int(m.group(2)), int(m.group(3)))
        raise KeyError(f"unknown proxy family {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def family_of(params) -> ProxyFamily:
    """Family lookup by parameter type (packed caches key on this)."""
    fam = _BY_PARAMS.get(type(params))
    if fam is None:
        raise KeyError(f"no proxy family registered for params type {type(params).__name__}")
    return fam


def family_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ------------------------------------------------------- built-in families
LINEAR = register_family(
    ProxyFamily(
        name="linear",
        params_cls=pm.LinearParams,
        train=lambda x, y, seed: pm.train_linear_svm(x, y),
        score=lambda p, x: np.asarray(pm.linear_score(p, x.astype(np.float32))),
        pack=pm.pack_linear,
    ),
    aliases=("svm",),
)

MLP1 = register_family(
    ProxyFamily(
        name="mlp1",
        params_cls=pm.MLPParams,
        train=lambda x, y, seed: pm.train_mlp(x, y, jax.random.PRNGKey(seed)),
        score=lambda p, x: np.asarray(pm.mlp_score(p, x.astype(np.float32))),
        pack=pm.pack_mlp,
    ),
    aliases=("mlp",),
)


def _widen(params: pm.LinearParams, lo: int, n_features: int) -> pm.LinearParams:
    """Linear params over columns ``[lo, lo + len(w))`` as params over all
    ``n_features`` columns: weight 0, mean 0 and scale 1 elsewhere, so every
    other column adds an exact 0 to the score."""
    w = np.zeros(n_features, np.float32)
    mean = np.zeros(n_features, np.float32)
    scale = np.ones(n_features, np.float32)
    hi = lo + int(np.shape(params.w)[0])
    w[lo:hi] = np.asarray(params.w, np.float32)
    mean[lo:hi] = np.asarray(params.mean, np.float32)
    scale[lo:hi] = np.asarray(params.scale, np.float32)
    return pm.LinearParams(w=w, b=np.float32(params.b), mean=mean, scale=scale)


def _narrow(params: pm.LinearParams, lo: int, hi: int) -> pm.LinearParams:
    return pm.LinearParams(w=params.w[lo:hi], b=params.b,
                           mean=params.mean[lo:hi], scale=params.scale[lo:hi])


@functools.lru_cache(maxsize=None)
def column_range(base: str, lo: int, hi: int) -> ProxyFamily:
    """The linear family restricted to the feature columns ``[lo, hi)``:
    trained and scored on those columns alone (bit-identical to ``base``
    on ``x[:, lo:hi]``), its params widened to every column (``_widen``),
    so packing, the fused kernel and every consumer of ``LinearParams`` run
    unchanged.  Named ``"linear[lo:hi]"``; ``get_family("svm[lo:hi]")``
    resolves to it."""
    if base != LINEAR.name or not 0 <= lo < hi:
        raise KeyError(f"no column-range family {base}[{lo}:{hi}]: only the "
                       f"linear family over a non-empty range has one")

    def train(x, y, seed):
        return _widen(LINEAR.train(x[:, lo:hi], y, seed), lo, x.shape[1])

    return ProxyFamily(
        name=f"{LINEAR.name}[{lo}:{hi}]",
        params_cls=pm.LinearParams,
        train=train,
        score=lambda p, x: LINEAR.score(_narrow(p, lo, hi), x[:, lo:hi]),
        pack=LINEAR.pack,
    )


def _packed_train_unsupported(x, y, seed):
    raise TypeError(
        "the 'packed1' family is a wire/device format, not a trainable one: "
        "deserialized plans carry folded PackedProxy params whose original "
        "training-side parameterization (standardizer, raw weights) is gone. "
        "Re-optimization happens where the builder lives (the coordinator), "
        "never on a host serving a deserialized artifact."
    )


# The already-folded depth-1 form itself, registered as a first-class family
# so DESERIALIZED scorer artifacts (kernels/ops.py::deserialize_scorer) are
# indistinguishable from locally-built plans everywhere downstream: family_of
# dispatch, the pack caches, the per-stage kernel fallback, and the scorer
# compile cache all work on PackedProxy params with pack == identity.
PACKED1 = register_family(
    ProxyFamily(
        name="packed1",
        params_cls=pm.PackedProxy,
        train=_packed_train_unsupported,
        score=lambda p, x: pm.packed_score(p, np.asarray(x, np.float32)),
        pack=lambda p: p,
    ),
)


# ------------------------------------------------- cascade-level packing
# Hidden widths are padded to a small bucket ladder so the fused kernel
# compiles one program per (F, H, P) shape class, not one per cascade.
HIDDEN_BUCKETS = (2, 4, 8, 16, 32, 64, 128)


def hidden_bucket(h: int) -> int:
    for b in HIDDEN_BUCKETS:
        if h <= b:
            return b
    # beyond the ladder: round up to the next multiple of the top bucket
    top = HIDDEN_BUCKETS[-1]
    return ((h + top - 1) // top) * top


class PackedCascade(NamedTuple):
    """Whole-cascade packed parameters, bucket-padded to static shapes.

    ``w1[(f, h, p)]`` is hidden weight ``h`` of stage ``p``; hidden slots
    ``h >= hidden[p]`` are zero-padded (``relu(0 + 0) = 0`` and a zero
    readout weight keeps them inert).  ``H`` is the shared hidden bucket:
    ``hidden_bucket(max(hidden))``.

    ``dtype`` names the WEIGHT storage format (DESIGN.md §3, quantized
    packed format).  ``"float32"`` is the seed format: ``w1``/``w2`` are
    fp32 and ``out_scale`` is None.  Under ``"int8"`` (weight-only
    symmetric quantization, ``quantize_cascade``) ``w1``/``w2`` hold
    integer codes and the per-column hidden scales are FOLDED away at
    quantization time — ``b1`` is pre-divided by the hidden scale and the
    hidden scale is pre-multiplied into the readout before ITS
    quantization — so execution needs exactly one dequantizing multiply:
    ``scores = (relu(x @ w1 + b1) @ w2) * out_scale + b2`` with
    ``out_scale`` the (P,) per-stage readout scales.  ``"fp8"`` is the
    simulated-e4m3 variant (values rounded to the fp8 grid, stored fp32 —
    accuracy studies on hardware without native fp8).
    """

    w1: np.ndarray  # (F, H, P) float32 | int8 codes
    b1: np.ndarray  # (H, P) float32 (scale-folded when quantized)
    w2: np.ndarray  # (H, P) float32 | int8 readout codes
    b2: np.ndarray  # (P,) float32
    hidden: Tuple[int, ...]  # true per-stage hidden widths
    families: Tuple[str, ...]  # per-stage family names
    dtype: str = "float32"  # weight storage format
    out_scale: Optional[np.ndarray] = None  # (P,) f32 readout scales (quantized only)

    @property
    def n_features(self) -> int:
        return int(self.w1.shape[0])

    @property
    def H(self) -> int:
        return int(self.w1.shape[1])

    @property
    def n_stages(self) -> int:
        return int(self.w1.shape[2])


def pack_cascade(param_list: Sequence[object], *,
                 pack_fn: Callable[[object], PackedProxy] = None) -> PackedCascade:
    """Pack every stage's params (any mix of families) into one
    bucket-padded (F, H, P) tensor set.  ``pack_fn`` overrides the per-proxy
    packer (e.g. ``kernels.ops.pack_proxy_cached`` to memoize the fold)."""
    if not param_list:
        raise ValueError("pack_cascade needs at least one proxy")
    packs: List[PackedProxy] = []
    fams: List[str] = []
    for p in param_list:
        fam = family_of(p)
        packs.append(pack_fn(p) if pack_fn is not None else fam.pack(p))
        fams.append(fam.name)
    F = packs[0].w1.shape[0]
    for pk in packs:
        if pk.w1.shape[0] != F:
            raise ValueError("all cascade stages must share the feature dim")
    H = hidden_bucket(max(pk.hidden for pk in packs))
    P = len(packs)
    w1 = np.zeros((F, H, P), np.float32)
    b1 = np.zeros((H, P), np.float32)
    w2 = np.zeros((H, P), np.float32)
    b2 = np.zeros(P, np.float32)
    for p, pk in enumerate(packs):
        h = pk.hidden
        w1[:, :h, p] = pk.w1
        b1[:h, p] = pk.b1
        w2[:h, p] = pk.w2
        b2[p] = pk.b2
    return PackedCascade(w1=w1, b1=b1, w2=w2, b2=b2,
                         hidden=tuple(pk.hidden for pk in packs),
                         families=tuple(fams))


def unpack_cascade(packed: PackedCascade, col: int) -> PackedProxy:
    """Inverse of ``pack_cascade`` for one stage: strips the hidden bucket
    padding and returns the stage's folded PackedProxy.  Exact (bit-for-bit)
    for fp32 cascades.  For a QUANTIZED cascade the returned proxy is the
    fp32 depth-1 MLP that computes the identical quantized function —
    integer codes as hidden weights, scale-folded bias, and the per-stage
    ``out_scale`` multiplied back into the readout — so reference scoring,
    regret estimation, and re-serialization of a deserialized quantized
    artifact all see exactly what the kernel computes."""
    h = packed.hidden[col]
    w1 = np.ascontiguousarray(packed.w1[:, :h, col], np.float32)
    w2 = np.ascontiguousarray(packed.w2[:h, col], np.float32)
    if packed.out_scale is not None:
        w2 = w2 * np.float32(packed.out_scale[col])
    return PackedProxy(
        w1=w1,
        b1=np.ascontiguousarray(packed.b1[:h, col]),
        w2=w2,
        b2=np.float32(packed.b2[col]),
        hidden=h,
    )


# ---------------------------------------------------- weight-only quantization
QUANT_DTYPES = ("float32", "int8", "fp8")
# bytes per weight element as MOVED by the kernel — fp8 is simulated
# (stored fp32 in this container) but modeled at its native width so the
# roofline sweep prices what real-hardware fp8 would move
QUANT_WEIGHT_BYTES = {"float32": 4, "int8": 1, "fp8": 1}


def _fp8_grid(x: np.ndarray) -> np.ndarray:
    """Round to the float8_e4m3 grid and back to fp32 (saturating — e4m3
    overflow encodes NaN, so inputs are pre-clipped to ±448)."""
    import ml_dtypes

    clipped = np.clip(x, -448.0, 448.0).astype(np.float32)
    return clipped.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)


def quantize_cascade(packed: PackedCascade, dtype: str = "int8") -> PackedCascade:
    """Weight-only symmetric quantization of a packed fp32 cascade, scales
    folded so the kernel dequantizes ONCE per tile (DESIGN.md §3):

    * per hidden column ``(h, p)``: ``s1[h,p] = max|w1[:,h,p]| / 127``;
      codes ``q1 = rint(w1 / s1)``.  Because ``relu(a·s) = s·relu(a)`` for
      ``s > 0``, the column scale commutes through the relu, so it is
      folded OUT of the hidden pass — ``b1`` becomes ``b1 / s1`` and
      ``s1`` multiplies into the readout weights — leaving the hidden GEMM
      pure integer codes.
    * per stage ``p`` over the scale-folded readout ``w2' = w2 · s1``:
      ``s2[p] = max|w2'[:,p]| / 127``; codes ``q2 = rint(w2' / s2)``;
      ``s2`` survives as ``out_scale``, the single dequantizing multiply
      ``scores = (relu(x @ q1 + b1') @ q2) · s2 + b2``.

    All-zero columns (hidden bucket padding) take scale 1 so the codes
    stay zero and the fold is the identity.  The linear family's exact
    ``relu(z) - relu(-z) == z`` embedding survives quantization: the +/-
    column pair shares one max-abs, hence one scale, and ``rint`` is odd,
    so the paired codes stay exact negations (tested).

    ``dtype="fp8"`` simulates float8_e4m3: same per-column scaling (to the
    e4m3 max of 448) and fold, values rounded to the fp8 grid but STORED
    fp32 — a fidelity study for hardware this container does not have; the
    roofline model prices it at 1 byte/weight, the wire ships fp32 bytes.
    """
    if packed.dtype != "float32":
        raise ValueError(f"cascade is already quantized ({packed.dtype})")
    if dtype == "float32":
        return packed
    if dtype not in QUANT_DTYPES:
        raise ValueError(f"unknown quantization dtype {dtype!r}; "
                         f"supported: {QUANT_DTYPES}")
    w1 = np.asarray(packed.w1, np.float32)
    w2 = np.asarray(packed.w2, np.float32)
    max_q = 127.0 if dtype == "int8" else 448.0
    a1 = np.max(np.abs(w1), axis=0)  # (H, P) per-hidden-column max
    s1 = np.where(a1 > 0, a1 / max_q, 1.0).astype(np.float32)
    if dtype == "int8":
        q1 = np.clip(np.rint(w1 / s1), -127, 127).astype(np.int8)
    else:
        q1 = _fp8_grid(w1 / s1)
    b1 = (np.asarray(packed.b1, np.float32) / s1).astype(np.float32)
    w2f = (w2 * s1).astype(np.float32)  # hidden scales folded into readout
    a2 = np.max(np.abs(w2f), axis=0)  # (P,) per-stage max
    s2 = np.where(a2 > 0, a2 / max_q, 1.0).astype(np.float32)
    if dtype == "int8":
        q2 = np.clip(np.rint(w2f / s2), -127, 127).astype(np.int8)
    else:
        q2 = _fp8_grid(w2f / s2)
    return PackedCascade(
        w1=q1, b1=b1, w2=q2, b2=np.asarray(packed.b2, np.float32),
        hidden=packed.hidden, families=packed.families,
        dtype=dtype, out_scale=s2,
    )


def cascade_kernel_operands(packed: PackedCascade):
    """Flatten (F, H, P) -> the kernel's two-GEMM operand layout.

    Returns ``(w1 (F, H*P), b1 (H*P,), w2 (H*P, P), b2 (P,))`` in h-major
    column order (column ``h*P + p`` is hidden unit ``h`` of stage ``p``);
    ``w2`` is the block-diagonal readout matrix of the second GEMM.
    Weight dtypes are preserved — a quantized cascade hands the kernel
    int8 code matrices (dequantized in-register via ``out_scale``, which
    travels separately on the scorer, not through this layout).
    """
    F, H, P = packed.w1.shape
    w1 = np.ascontiguousarray(packed.w1.reshape(F, H * P))
    b1 = np.ascontiguousarray(packed.b1.reshape(H * P))
    w2 = np.zeros((H * P, P), packed.w2.dtype)
    w2[np.arange(H * P), np.tile(np.arange(P), H)] = packed.w2.reshape(H * P)
    return w1, b1, w2, np.asarray(packed.b2, np.float32)
