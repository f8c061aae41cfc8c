"""The one general traffic generator: a traffic file's parameters and a seed
in, per-node data shards out.

A traffic mix is a data file (``traffic/<name>.json``); a later PR adds a file,
never code. Two generators cover the job kinds the repository trains:

- ``lm_markov``: next-token prediction over a near-deterministic Markov chain
  on the configuration's vocabulary (the job of ``FederatedDataset
  .synthetic_lm``, regenerated here from ``--seed`` with one successor table
  per run and ``docs_per_node`` sequences of ``seq_len`` tokens per node);
- ``vision_dirichlet``: class prototypes + Gaussian noise squashed to [0, 1]
  (the job of ``bench_suite._config3_measure``), label-skewed per node the way
  Hsu et al. 2019 (arXiv:1909.06335) define it: each node draws its class
  proportions from Dir(alpha) and its ``samples_per_node`` labels from them —
  so every node holds exactly ``samples_per_node`` samples and a round is
  exactly ``samples_per_node // batch_size`` steps.

The program's own generators are ``p2pfl_tpu.learning.dataset`` (same maths;
they partition one pool, so shard sizes vary with the seed). Everything here is
numpy on the host, counted as set-up; the federation stages it once.
"""

from __future__ import annotations

import numpy as np


def lm_markov(p: dict, vocab_size: int, seed: int, *, n_nodes: int, seq_len: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    succ = rng.permutation(vocab_size)

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        toks = np.empty((n, seq_len + 1), dtype=np.int32)
        toks[:, 0] = rng.integers(0, vocab_size, size=n)
        follow = rng.random((n, seq_len)) < p["determinism"]
        rand = rng.integers(0, vocab_size, size=(n, seq_len))
        for t in range(seq_len):
            toks[:, t + 1] = np.where(follow[:, t], succ[toks[:, t]], rand[:, t])
        return toks[:, :-1], toks[:, 1:].copy()

    x, y = make(n_nodes * p["docs_per_node"])
    xt, yt = make(n_nodes * p["test_docs_per_node"])
    d, dt = p["docs_per_node"], p["test_docs_per_node"]
    return [
        {"x": x[i * d:(i + 1) * d], "y": y[i * d:(i + 1) * d],
         "x_test": xt[i * dt:(i + 1) * dt], "y_test": yt[i * dt:(i + 1) * dt]}
        for i in range(n_nodes)
    ]


def vision_dirichlet(p: dict, num_classes: int, input_shape, seed: int, *, n_nodes: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    dim = int(np.prod(input_shape))
    protos = rng.normal(0.0, p["proto_scale"], size=(num_classes, p["modes"], dim)).astype(np.float32)

    def make(labels: np.ndarray) -> np.ndarray:
        mode = rng.integers(0, p["modes"], size=len(labels))
        x = protos[labels, mode] + rng.standard_normal((len(labels), dim), dtype=np.float32) * p["noise"]
        return (1.0 / (1.0 + np.exp(-x))).reshape((len(labels), *input_shape)).astype(np.float32)

    shards = []
    for _ in range(n_nodes):
        props = rng.dirichlet([p["dirichlet_alpha"]] * num_classes)
        y = rng.choice(num_classes, size=p["samples_per_node"], p=props).astype(np.int32)
        yt = rng.integers(0, num_classes, size=p["test_samples_per_node"]).astype(np.int32)
        shards.append({"x": make(y), "y": y, "x_test": make(yt), "y_test": yt})
    return shards


def generate(traffic: dict, model_cfg: dict, seed: int) -> list[dict]:
    """Per-node shards ``{"x", "y", "x_test", "y_test"}`` (numpy) for a traffic
    file under a model configuration."""
    kind, params = traffic["generator"], traffic["data"]
    if kind == "lm_markov":
        return lm_markov(
            params, model_cfg["vocab_size"], seed,
            n_nodes=traffic["n_nodes"], seq_len=traffic["seq_len"],
        )
    if kind == "vision_dirichlet":
        return vision_dirichlet(
            params, model_cfg["num_classes"], tuple(model_cfg["input_shape"]), seed,
            n_nodes=traffic["n_nodes"],
        )
    raise ValueError(f"traffic generator {kind!r} is not one of lm_markov, vision_dirichlet")


def as_datasets(shards: list[dict], num_classes: int) -> list:
    """The shards as the program's own ``FederatedDataset`` objects."""
    from p2pfl_tpu.learning.dataset import FederatedDataset

    return [
        FederatedDataset(s["x"], s["y"], s["x_test"], s["y_test"], num_classes)
        for s in shards
    ]
