"""UDF family ``mlp``: the seeded synthetic record process and one MLP
classifier per label column, trained at set-up (``workload.py``).

A configuration that names no ``udf_family`` gets this one.  It reads the
configuration's ``n_features``, ``n_latent``, ``n_columns``,
``n_classes``, ``correlation``, ``label_noise``, ``feature_noise``,
``model_rows``, ``udf_train_rows``, ``udf_train_steps``, ``udf_hidden``,
``udf_depth``, ``udf_pad_rows``, ``tile`` and ``model_seed``.  Records
are float32 feature rows, and the proxies score those rows as they are.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

REF_BLOCK_ROWS = 1 << 14    # rows per UDF call of the reference


def build(cfg: dict, load: Callable):
    """The family's object for ``cfg``; ``load(name)`` gives a module of
    the benchmark's own directory."""
    return MlpFamily(cfg, load("workload"), load("counts"))


class MlpFamily:
    def __init__(self, cfg: dict, wl, counts):
        self.cfg = cfg
        self.wl = wl
        self.counts = counts
        seed = int(cfg["model_seed"])
        proc, x_model, truth = wl.make_process(
            n_features=cfg["n_features"], n_latent=cfg["n_latent"],
            n_columns=cfg["n_columns"], n_classes=cfg["n_classes"],
            correlation=cfg["correlation"], label_noise=cfg["label_noise"],
            feature_noise=cfg["feature_noise"], n_rows=cfg["model_rows"],
            seed=seed)
        self.process = proc
        # the rows the query's values and the optimization sample come from
        self.x_model = x_model
        idx = np.random.RandomState(seed).choice(
            len(x_model), min(cfg["udf_train_rows"], len(x_model)),
            replace=False)
        params = wl.train_udfs(
            x_model[idx], truth[idx], hidden=cfg["udf_hidden"],
            depth=cfg["udf_depth"], n_classes=cfg["n_classes"],
            steps=cfg["udf_train_steps"], seed=seed)
        self.fwd = wl.UdfForward(params, cfg["udf_pad_rows"])
        self.udf_dims = wl.udf_layer_dims(
            cfg["n_features"], cfg["udf_hidden"], cfg["udf_depth"],
            cfg["n_classes"])
        self.n_proxy_features = int(cfg["n_features"])
        # the served UDF callables: ``labels = udfs[j](rows)``
        self.udfs = [lambda x, j=j: self.fwd(j, x)
                     for j in range(self.fwd.n_udfs)]

    def block_sampler(self, block_rows: int):
        """``draw(words, b) -> rows`` of block ``b`` of the stream keyed by
        the two uint32 ``words``: float32 rows of ``n_features``."""
        return self.process.block_sampler(block_rows)

    def warm(self) -> None:
        """Every UDF batch shape the engine can send: tiles of ``tile``
        rows or fewer, padded to multiples of ``udf_pad_rows``."""
        pad = self.fwd.pad_rows
        for j in range(self.fwd.n_udfs):
            for n in range(pad, int(self.cfg["tile"]) + 1, pad):
                self.fwd(j, np.zeros((n, self.n_proxy_features), np.float32))

    def labels_for_query(self, x: np.ndarray) -> List[np.ndarray]:
        """Every UDF's labels over ``x``, in tiles (the query's values
        are chosen from them)."""
        return [self.wl.labels_in_blocks(self.fwd, j, x, int(self.cfg["tile"]))
                for j in range(self.fwd.n_udfs)]

    def orig_labels(self, x: np.ndarray) -> List[np.ndarray]:
        """Every UDF on every row (the ORIG plan's work), in blocks."""
        return [self.wl.labels_in_blocks(self.fwd, j, x, REF_BLOCK_ROWS)
                for j in range(self.fwd.n_udfs)]

    def proxy_inputs(self, rows: np.ndarray) -> np.ndarray:
        return rows

    def udf_flops(self, rows: float) -> float:
        return self.counts.mlp_flops(rows, self.udf_dims)

    def extra_checks(self, x_window: np.ndarray, limits: dict) -> Dict[str, tuple]:
        return {}
