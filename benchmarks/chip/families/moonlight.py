"""UDF family ``moonlight``: Moonlight-16B-A3B, cut to one chip's share of
an expert-parallel deployment, as the query's sequence-classifier UDFs
over text records.

Records are float32 rows: a stored sketch of ``sketch_buckets`` columns
(log1p counts of the record's tokens hashed into buckets, made once at
ingest), then ``record_tokens`` token ids of the vocabulary slice (exact
in float32), right-padded with id 0.  The proxies read the sketch only
(``proxy_kind`` ``"svm[0:128]"``); the UDFs read the tokens.

The record process (``model_seed``): ``topics`` latent topics with
Zipf(``topic_zipf``) popularity; a record's dominant topic is drawn by
popularity and its topic mixture from a Dirichlet leaning on it; each
token draws a topic from the mixture, then a rank from Zipf(``token_zipf``)
over that topic's own order of the ids; lengths are lognormal (median
``length_median``, sigma ``length_sigma``) clipped to ``length_min``..
``length_max``.

Each UDF is the program's ``models/classifier.py`` with weights from its
own seed (``model_seed + j``), run by a ``DeviceUDF`` with the record
buckets ``udf_buckets``; the three share one compiled program a bucket.
Its head is a ridge probe fitted at set-up on the pooled states of
``head_rows`` rows drawn for it alone, to targets binned from the topic
mixture along three directions of cosine ``correlation`` with
``label_noise``.

The served callables keep a seeded sample of rows and the logits the
timed path gave them (``logit_rows`` a UDF, the latest); ``extra_checks``
compares those found among the window's rows with the float32 reference
(``reference_moonlight.py``).  ``fp8_weights`` (a control: the nearest
float format below the stated bf16) serves every bf16 weight rounded to
float8 e4m3 while the reference keeps the bf16 weights.

Off a TPU, where only the harness's chip-less rehearsals reach this
module (``run_cell.py`` exits first), ``REHEARSAL`` (tiny widths,
float32) replaces the configuration's widths, so those rehearsals run the
same flow in a test's time; ``build`` says so on stderr.
"""
from __future__ import annotations

import collections
import sys
from typing import Callable, Dict, List

import numpy as np

REF_BLOCK_ROWS = 1 << 13    # rows per ORIG labeling call

# the layout at widths a CPU runs in a test's time: 3 layers, 4 of 16
# experts held, 32-token records, float32
REHEARSAL = {
    "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "n_routed_experts": 4,
    "published": {"num_hidden_layers": 27, "n_routed_experts": 16,
                  "vocab_size": 163840},
    "record_tokens": 32, "length_median": 12, "length_min": 4,
    "length_max": 32, "head_rows": 1024, "udf_buckets": [4, 8, 16],
    "logit_sample_rate": 1.0, "dtype": "float32"}


def build(cfg: dict, load: Callable):
    """The family of ``cfg``; off a TPU (the harness's chip-less
    rehearsals) at the ``REHEARSAL`` widths, since the published ones do
    not run on a CPU in a test's time."""
    import jax

    if jax.default_backend() != "tpu":
        print("moonlight family off a TPU: a rehearsal at the REHEARSAL "
              "widths, not the configuration's", file=sys.stderr, flush=True)
        cfg = {**cfg, **REHEARSAL}
    return MoonlightFamily(cfg, load("counts_moonlight"),
                           load("reference_moonlight"))


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of the configuration: the published
    config's keys by their Hugging Face names, the router over
    ``published.n_routed_experts``."""
    from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig

    fixed = {"model_type": "deepseek_v3", "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True,
             "q_lora_rank": None, "hidden_act": "silu", "moe_layer_freq": 1,
             "attention_bias": False}
    for k, v in fixed.items():
        if cfg[k] != v:
            raise ValueError(f"the moonlight family runs {k}={v!r}, not {cfg[k]!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("MLA has one latent KV for every head")
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        attention=AttentionConfig(
            kind="mla", num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
            q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(
            num_experts=cfg["published"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            expert_ff=cfg["moe_intermediate_size"],
            num_shared=cfg["n_shared_experts"],
            first_dense=cfg["first_k_dense_replace"],
            dense_ff=cfg["intermediate_size"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"])),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg.get("dtype", "bfloat16"),
        source=cfg["source"])


class TopicProcess:
    """The record process, fixed by ``model_seed``; ``sampler(n)`` draws
    n fresh records (and their topic mixtures) on the device."""

    def __init__(self, cfg: dict):
        rng = np.random.RandomState(int(cfg["model_seed"]))
        self.cfg = cfg
        v, k = int(cfg["vocab_size"]), int(cfg["topics"])
        self.order = np.stack([rng.permutation(v - 1) + 1 for _ in range(k)]
                              ).astype(np.int32)         # (topics, ids)
        self.bucket = rng.randint(0, cfg["sketch_buckets"], v).astype(np.int32)
        ranks = np.arange(1, v, dtype=np.float64)
        cdf = np.cumsum(ranks ** -float(cfg["token_zipf"]))
        self.rank_cdf = (cdf / cdf[-1]).astype(np.float32)
        pop = np.arange(1, k + 1, dtype=np.float64) ** -float(cfg["topic_zipf"])
        self.log_pop = np.log(pop / pop.sum()).astype(np.float32)
        self._jit = {}

    def sampler(self, n: int):
        """Jitted ``draw(words, b) -> (rows (n, F) float32, mixtures (n,
        topics))``: block ``b`` of the stream keyed by two uint32 words,
        drawn in chunks of at most 4,096 records."""
        if n not in self._jit:
            import jax

            self._jit[n] = jax.jit(self._draw_fn(n))
        return self._jit[n]

    def _draw_fn(self, n: int):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        s, nb = int(cfg["record_tokens"]), int(cfg["sketch_buckets"])
        k = len(self.log_pop)
        chunk = int(np.gcd(n, 4096))
        order = jnp.asarray(self.order)
        bucket = jnp.asarray(self.bucket)
        rank_cdf = jnp.asarray(self.rank_cdf)
        log_pop = jnp.asarray(self.log_pop)
        lo, hi = int(cfg["length_min"]), int(cfg["length_max"])
        mu, sigma = np.log(float(cfg["length_median"])), float(cfg["length_sigma"])
        conc, lean = float(cfg["topic_concentration"]), float(cfg["topic_lean"])

        def one_chunk(key):
            kd, km, kl, kt, kr = jax.random.split(key, 5)
            dom = jax.random.categorical(kd, log_pop, shape=(chunk,))
            mix = jax.random.dirichlet(
                km, conc + lean * jax.nn.one_hot(dom, k, dtype=jnp.float32))
            length = jnp.clip(jnp.round(jnp.exp(
                mu + sigma * jax.random.normal(kl, (chunk,)))), lo, hi)
            cum = jnp.cumsum(mix, axis=1)
            u = jax.random.uniform(kt, (chunk, s, 1))
            topic = jnp.minimum(jnp.sum(u > cum[:, None, :], axis=-1), k - 1)
            rank = jnp.searchsorted(rank_cdf, jax.random.uniform(kr, (chunk, s)))
            rank = jnp.minimum(rank, order.shape[1] - 1)
            ids = order[topic, rank]
            tokens = jnp.where(jnp.arange(s)[None, :] < length[:, None], ids, 0)
            seg = (jnp.arange(chunk)[:, None] * nb + bucket[tokens]).reshape(-1)
            counts = jax.ops.segment_sum((tokens > 0).reshape(-1).astype(
                jnp.float32), seg, num_segments=chunk * nb).reshape(chunk, nb)
            rows = jnp.concatenate([jnp.log1p(counts),
                                    tokens.astype(jnp.float32)], axis=1)
            return rows, mix

        def draw(words, b):
            key = jax.random.fold_in(jax.random.wrap_key_data(words), b)
            rows, mix = jax.lax.map(one_chunk, jax.random.split(key, n // chunk))
            return rows.reshape(n, -1), mix.reshape(n, k)

        return draw

    def block_sampler(self, block_rows: int):
        draw = self.sampler(block_rows)
        return lambda words, b: draw(words, b)[0]


def label_directions(n_topics: int, n_columns: int, correlation: float,
                     rng: np.random.RandomState) -> np.ndarray:
    """Unit directions in topic space, consecutive ones at cosine
    ``correlation`` through a shared base (as ``workload.make_process``)."""
    base = rng.randn(n_topics)
    base /= np.linalg.norm(base)
    dirs = np.empty((n_columns, n_topics))
    for j in range(n_columns):
        fresh = rng.randn(n_topics)
        fresh -= (fresh @ base) * base
        fresh /= np.linalg.norm(fresh) + 1e-9
        dirs[j] = correlation * base + np.sqrt(1 - correlation ** 2) * fresh
    return dirs


class MoonlightFamily:
    def __init__(self, cfg: dict, counts, reference):
        import jax
        import jax.numpy as jnp

        from repro.core.query import DeviceUDF
        from repro.models import classifier

        self.cfg = cfg
        self.counts = counts
        self.reference = reference
        self.classifier = classifier
        self.mcfg = model_config(cfg)
        self.first = int(cfg["held_first"])
        self.n_held = int(cfg["n_routed_experts"])
        self.token_col = int(cfg["sketch_buckets"])
        self.n_proxy_features = self.token_col + int(cfg["record_tokens"])
        seed = int(cfg["model_seed"])
        n_udfs, n_cls = int(cfg["n_columns"]), int(cfg["n_classes"])

        self.process = TopicProcess(cfg)

        def draw(n, stream):
            words = np.random.SeedSequence([seed, stream]).generate_state(
                2, np.uint32)
            rows, mix = self.process.sampler(n)(words, np.int32(0))
            return np.ascontiguousarray(np.asarray(rows)), mix

        # the heads are fitted on rows of their own: labels of the rows a
        # head was fitted on are easier to predict than a fresh record's,
        # so an optimization sample drawn from them would calibrate the
        # proxies on the wrong distribution
        x_fit, mix = draw(int(cfg["head_rows"]), 3)
        truth = self._targets(np.asarray(mix, np.float64), seed)
        self.x_model, _ = draw(int(cfg["model_rows"]), 2)

        init = jax.jit(lambda k: classifier.init(
            k, self.mcfg, n_held=self.n_held, n_classes=n_cls))
        self.params = [init(jax.random.PRNGKey(seed + j)) for j in range(n_udfs)]
        pool = jax.jit(lambda p, x: classifier.pooled(
            p, self.mcfg, x[:, self.token_col:].astype(jnp.int32),
            held_first=self.first)[0])
        tile = int(cfg["tile"])
        for j, p in enumerate(self.params):
            states = np.concatenate(jax.device_get(
                [pool(p, x_fit[s:s + tile]) for s in range(0, len(x_fit), tile)]
            )).astype(np.float64)
            w, b = ridge(states, np.eye(n_cls)[truth[:, j]], float(cfg["ridge"]))
            p["head_w"] = jnp.asarray(w, jnp.float32)
            p["head_b"] = jnp.asarray(b, jnp.float32)

        served = self.params
        if cfg.get("fp8_weights"):
            dt = jnp.dtype(self.mcfg.dtype)
            served = [jax.tree.map(lambda a: _fp8(a, dt), p)
                      for p in self.params]
        forward = classifier.row_forward(self.mcfg, held_first=self.first,
                                         token_col=self.token_col)
        self.dev = [DeviceUDF(forward, p, cfg["udf_buckets"]) for p in served]
        self._rng = np.random.RandomState(seed + 7)
        self.captured = [collections.deque(maxlen=int(cfg["logit_rows"]))
                         for _ in range(n_udfs)]
        self.udfs = [lambda x, j=j: self._served(j, x) for j in range(n_udfs)]

    # ---------------------------------------------------------- set-up
    def _targets(self, mix: np.ndarray, seed: int) -> np.ndarray:
        """(rows, UDFs) classes: the mixture along each label direction,
        standardized, plus ``label_noise``, binned at its quantiles."""
        cfg = self.cfg
        rng = np.random.RandomState(seed + 3)
        dirs = label_directions(mix.shape[1], int(cfg["n_columns"]),
                                float(cfg["correlation"]), rng)
        score = mix @ dirs.T
        score = (score - score.mean(0)) / (score.std(0) + 1e-12)
        score += float(cfg["label_noise"]) * rng.randn(*score.shape)
        n_cls = int(cfg["n_classes"])
        cuts = np.quantile(score, np.linspace(0, 1, n_cls + 1)[1:-1], axis=0)
        return np.stack([np.digitize(score[:, j], cuts[:, j])
                         for j in range(score.shape[1])], axis=1)

    # ---------------------------------------------------------- hooks
    def block_sampler(self, block_rows: int):
        return self.process.block_sampler(block_rows)

    def warm(self) -> None:
        """Every record bucket of every UDF (one compiled program a bucket
        serves the three)."""
        for dev in self.dev:
            for b in dev.buckets:
                dev(np.zeros((b, self.n_proxy_features), np.float32))

    def _served(self, j: int, x: np.ndarray) -> np.ndarray:
        lg = self.dev[j].logits(x)
        keep = self._rng.random_sample(len(x)) < float(self.cfg["logit_sample_rate"])
        for i in np.flatnonzero(keep):
            self.captured[j].append((np.array(x[i]), lg[i]))
        return np.argmax(lg, axis=1)

    def labels_for_query(self, x: np.ndarray) -> List[np.ndarray]:
        return self.orig_labels(x)

    def orig_labels(self, x: np.ndarray) -> List[np.ndarray]:
        return [np.concatenate([dev(x[s:s + REF_BLOCK_ROWS])
                                for s in range(0, len(x), REF_BLOCK_ROWS)])
                for dev in self.dev]

    def proxy_inputs(self, rows: np.ndarray) -> np.ndarray:
        return rows

    # ---------------------------------------------------------- counts
    def calls(self, t0_s: float = -np.inf, t1_s: float = np.inf) -> List[tuple]:
        """Every UDF call made in [t0_s, t1_s) on the host clock (seconds):
        (records, record slots, named counts), all UDFs."""
        out = []
        for dev in self.dev:
            for t_ms, n, slots, stats in dev.counters:
                if t0_s <= t_ms * 1e-3 < t1_s:
                    out.append((n, slots, self.classifier.unpack_stats(
                        stats, self.mcfg, self.n_held)))
        return out

    def flops(self, calls: List[tuple], padded: bool) -> float:
        """FLOPs of ``calls``: the padded work computed, or the valid work
        their records needed."""
        return sum((self.counts.call_work(self.cfg, st, slots, padded)[0]
                    for _n, slots, st in calls), 0.0)

    def least_time_s(self, calls: List[tuple], peaks: dict) -> float:
        """The roofline of ``calls``' padded work, call by call: the larger
        of FLOPs over the bf16 peak and bytes over the HBM bandwidth."""
        total = 0.0
        for _n, slots, st in calls:
            f, b = self.counts.call_work(self.cfg, st, slots, padded=True)
            total += max(f / peaks["bf16_flop_per_s"], b / peaks["hbm_bytes_per_s"])
        return total

    def udf_flops(self, rows: float) -> float:
        """Valid-token FLOPs of ``rows`` records at the mean valid work a
        record of the served calls needed."""
        calls = self.calls()
        n = sum(c[0] for c in calls)
        return rows * self.flops(calls, padded=False) / n if n else 0.0

    # ---------------------------------------------------------- check
    def extra_checks(self, x_window: np.ndarray, limits: dict) -> Dict[str, tuple]:
        """Logits the timed path gave window records against the float32
        reference on the same rows (``logit_*``)."""
        load = sum(st["expert_tokens"] for _n, _s, st in self.calls())
        print(f"held-expert load, max over mean a MoE layer, all served "
              f"calls: {np.round(load.max(1) / load.mean(1), 3).tolist()}",
              file=sys.stderr, flush=True)
        index = {row.tobytes() for row in x_window}
        err = 0.0
        flips = 0
        n_min = None
        for j, params in enumerate(self.params):
            got = [(r, lg) for r, lg in self.captured[j] if r.tobytes() in index]
            n_min = len(got) if n_min is None else min(n_min, len(got))
            if not got:
                continue
            rows = np.stack([r for r, _ in got])
            prog = np.stack([lg for _, lg in got]).astype(np.float64)
            ref = self.reference.logits(params, self.cfg, rows[:, self.token_col:],
                                        first=self.first)
            # the typical gap between a row's class logits
            scale = np.sqrt(np.mean((ref - ref.mean(axis=1, keepdims=True)) ** 2))
            rel = np.abs(prog - ref).max(axis=1) / scale
            err = max(err, float(rel.max()))
            top2 = np.sort(ref, axis=1)[:, -2:]
            margin = (top2[:, 1] - top2[:, 0]) / scale
            decided = margin > limits["logit_err_max"]
            flips += int(np.count_nonzero(
                decided & (prog.argmax(1) != ref.argmax(1))))
        return {"logit_rows": (n_min or 0, ">=", limits["logit_rows_min"]),
                "logit_err": (err, "<=", limits["logit_err_max"]),
                "logit_argmax_flips": (flips, "<=", limits["logit_flips_max"])}


def ridge(x: np.ndarray, y: np.ndarray, lam: float):
    """Ridge regression of y on x with an intercept; the penalty is ``lam``
    times the mean feature variance."""
    xm, ym = x.mean(0), y.mean(0)
    xc = x - xm
    gram = xc.T @ xc
    w = np.linalg.solve(gram + lam * np.trace(gram) / len(gram)
                        * np.eye(len(gram)), xc.T @ (y - ym))
    return w, ym - xm @ w


def _fp8(a, dtype):
    """A weight of the model's dtype rounded to float8 e4m3 (exact in that
    dtype again); other arrays (norms, bias and head of a bf16 model) as
    they are."""
    import jax.numpy as jnp

    if a.dtype != dtype:
        return a
    return a.astype(jnp.float8_e4m3fn).astype(dtype)
