"""The benchmark's own workload: a seeded record process, the ML UDFs
(the user code of an ML-UDF query) and the query builder.

Copied from the program's synthetic stand-in (``data/synthetic.py``:
``make_dataset``, ``_train_udf_model``/``make_udfs``, ``make_query``) so
that no later change to the program can change the yardstick.  What
differs from the original:

* the generative process (latent readouts, feature map, class bounds)
  and the served records are split: ``RecordProcess`` is fixed by the
  configuration's ``model_seed``; ``RecordProcess.block_sampler`` draws
  fresh records of the same process on the device, block by block, from
  any run seed;
* the UDFs of one configuration train in one jitted call (``vmap`` over
  the label columns) and share one jitted forward, so every UDF batch
  shape compiles once for all of them;
* a UDF forward pads its batch to a multiple of ``pad_rows`` rows, as the
  original does, and returns its labels as numpy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass
class RecordProcess:
    """Latent ``z ~ N(0, I)``, features ``tanh(z W + noise)``.  A served
    record's labels are what the UDFs say of it (the ORIG plan), as for
    a real corpus."""

    w_feat: np.ndarray       # (n_latent, F)
    feature_noise: float

    def block_sampler(self, block_rows: int):
        """A jitted ``draw(words, b) -> x`` of ``block_rows`` fresh records
        (float32 features) of this process: block ``b`` of the stream
        keyed by the two uint32 ``words``.  ``b`` is traced, so every
        block runs one compiled program."""
        cache = self.__dict__.setdefault("_samplers", {})
        if block_rows not in cache:
            import jax
            import jax.numpy as jnp

            w = jnp.asarray(self.w_feat)
            k, f = w.shape
            noise_scale = float(self.feature_noise)

            def draw(words, b):
                key = jax.random.fold_in(jax.random.wrap_key_data(words), b)
                kz, kn = jax.random.split(key)
                z = jax.random.normal(kz, (block_rows, k), jnp.float32)
                noise = jax.random.normal(kn, (block_rows, f), jnp.float32)
                return jnp.tanh(jnp.dot(z, w, precision="highest")
                                + noise_scale * noise)

            cache[block_rows] = jax.jit(draw)
        return cache[block_rows]


def make_process(*, n_features: int, n_latent: int, n_columns: int,
                 n_classes: int, correlation: float, label_noise: float,
                 feature_noise: float, n_rows: int, seed: int):
    """The process of ``make_dataset`` and its first ``n_rows`` records
    (the rows the UDFs, the query and the optimization sample come from).
    ``correlation`` is the cosine between consecutive readout directions."""
    rng = np.random.RandomState(seed)
    z = rng.randn(n_rows, n_latent).astype(np.float32)
    w = rng.randn(n_latent, n_features).astype(np.float32) / np.sqrt(n_latent)
    x = np.tanh(z @ w + feature_noise
                * rng.randn(n_rows, n_features).astype(np.float32))
    dirs = np.empty((n_columns, n_latent), np.float32)
    base = rng.randn(n_latent)
    base /= np.linalg.norm(base)
    for j in range(n_columns):
        fresh = rng.randn(n_latent)
        fresh /= np.linalg.norm(fresh)
        fresh = fresh - (fresh @ base) * base
        fresh /= np.linalg.norm(fresh) + 1e-9
        d = correlation * base + np.sqrt(max(1 - correlation ** 2, 0.0)) * fresh
        dirs[j] = d / np.linalg.norm(d)
    truth = np.empty((n_rows, n_columns), np.int64)
    for j in range(n_columns):
        score = z @ dirs[j] + label_noise * rng.randn(n_rows).astype(np.float32)
        qs = np.quantile(score, np.linspace(0, 1, n_classes + 1)[1:-1])
        truth[:, j] = np.digitize(score, qs)
    return RecordProcess(w, feature_noise), x.astype(np.float32), truth


# --------------------------------------------------------------------- UDFs
def udf_layer_dims(n_features: int, hidden: int, depth: int,
                   n_classes: int) -> List[int]:
    return [n_features] + [hidden] * depth + [n_classes]


def _logits(params, x):
    import jax

    h = x
    for w, b in params[:-1]:
        h = jax.nn.relu(h @ w + b)
    w, b = params[-1]
    return h @ w + b


def train_udfs(x: np.ndarray, labels: np.ndarray, *, hidden: int, depth: int,
               n_classes: int, steps: int, seed: int):
    """Train one MLP classifier per label column in ONE jitted call
    (``vmap`` over columns): the body of ``_train_udf_model``, momentum
    SGD on the softmax cross-entropy.  Returns the stacked params, each
    leaf with a leading column axis, on the device."""
    import jax
    import jax.numpy as jnp

    dims = udf_layer_dims(x.shape[1], hidden, depth, n_classes)
    n_cols = labels.shape[1]

    def init(key):
        ks = jax.random.split(key, len(dims) - 1)
        return [(jax.random.normal(ks[i], (dims[i], dims[i + 1]))
                 / jnp.sqrt(dims[i]), jnp.zeros(dims[i + 1]))
                for i in range(len(dims) - 1)]

    def fit(key, xx, yy):
        p0 = init(key)

        def loss_fn(p):
            lg = _logits(p, xx)
            return jnp.mean(jax.nn.logsumexp(lg, axis=-1)
                            - jnp.take_along_axis(lg, yy[:, None], 1)[:, 0])

        def step(carry, _):
            p, m = carry
            g = jax.grad(loss_fn)(p)
            m = jax.tree.map(lambda mm, gg: 0.9 * mm + gg, m, g)
            p = jax.tree.map(lambda pp, mm: pp - 0.05 * mm, p, m)
            return (p, m), None

        m0 = jax.tree.map(jnp.zeros_like, p0)
        (p, _), _ = jax.lax.scan(step, (p0, m0), None, length=steps)
        return p

    keys = jnp.stack([jax.random.PRNGKey(seed + j) for j in range(n_cols)])
    run = jax.jit(jax.vmap(fit, in_axes=(0, None, 1)))
    return run(keys, jnp.asarray(x), jnp.asarray(labels.astype(np.int32)))


class UdfForward:
    """One jitted forward shared by every UDF of a configuration:
    ``labels = argmax(mlp(params_j, x))`` on a batch padded to a multiple
    of ``pad_rows`` rows."""

    def __init__(self, stacked_params, pad_rows: int):
        import jax
        import jax.numpy as jnp

        self.pad_rows = int(pad_rows)
        self.n_udfs = int(stacked_params[0][0].shape[0])
        self.params = [jax.tree.map(lambda a, j=j: a[j], stacked_params)
                       for j in range(self.n_udfs)]
        self._placed = {}

        def udf_forward(p, xx):
            return jnp.argmax(_logits(p, xx), axis=-1)

        self._predict = jax.jit(udf_forward)

    def padded(self, n: int) -> int:
        return max(self.pad_rows, -(-n // self.pad_rows) * self.pad_rows)

    def _params_on(self, j: int):
        """UDF ``j``'s params on the device new arrays land on (a serving
        host inside ``jax.default_device(d)`` runs its UDFs on ``d``)."""
        import jax

        dev = jax.config.jax_default_device or jax.devices()[0]
        key = (j, dev.platform, dev.id)
        if key not in self._placed:
            self._placed[key] = jax.device_put(self.params[j], dev)
        return self._placed[key], dev

    def __call__(self, j: int, x: np.ndarray) -> np.ndarray:
        import jax

        x = np.asarray(x, np.float32)
        n = x.shape[0]
        xp = np.zeros((self.padded(n), x.shape[1]), np.float32)
        xp[:n] = x
        params, dev = self._params_on(j)
        return np.asarray(self._predict(params, jax.device_put(xp, dev)))[:n]


def labels_in_blocks(fwd: UdfForward, j: int, x: np.ndarray,
                     block: int) -> np.ndarray:
    """UDF ``j`` over ``x`` in blocks of ``block`` rows (a shape the
    served path already compiled)."""
    out = [fwd(j, x[s:s + block]) for s in range(0, len(x), block)]
    return np.concatenate(out) if out else np.empty(0, np.int64)


# ------------------------------------------------------------------- query
def choose_values(label_cols: Sequence[np.ndarray], n_classes: int,
                  target_selectivity: float, seed: int) -> List[List[int]]:
    """``make_query``'s value choice: each predicate takes classes until
    its selectivity reaches the target; later predicates take the classes
    most positively associated with the conjunction before them."""
    rng = np.random.RandomState(seed)
    prefix = np.ones(len(label_cols[0]), bool)
    chosen_all = []
    for labels in label_cols:
        vals, counts = np.unique(labels, return_counts=True)
        fracs = counts / counts.sum()
        if chosen_all and prefix.any():
            cond = np.asarray([np.mean(labels[prefix] == v) for v in vals])
            order = np.argsort(-(cond / np.maximum(fracs, 1e-9)))
        else:
            order = rng.permutation(len(vals))
        chosen, tot = [], 0.0
        for i in order:
            if tot >= target_selectivity:
                break
            chosen.append(int(vals[i]))
            tot += fracs[i]
        chosen_all.append(chosen)
        prefix &= np.isin(labels, chosen)
    return chosen_all
