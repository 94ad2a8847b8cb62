"""Federated training data made from the seed: the benchmark's own copy of
the program's synthetic corpora and of the paper's label-shard protocol
(``repro.data.synthetic``, ``repro.data.partition``,
``repro.data.pipeline.build_federated_data`` and
``build_lm_federated_data``), so that a change to the program cannot move
the yardstick.  A training mix's ``data`` block names its ``kind``:

* ``tokens``: topic-conditioned Markov token streams (each topic owns a
  bigram rule over a slice of the vocabulary, 10% of tokens jump at
  random); the topic is the label;
* ``images``: class-structured images (a unit prototype per class, a
  low-rank class-conditional part, Gaussian noise), CIFAR-10's shapes.

Rows are dealt to clients by label, 2 label shards each; the server draws
a share of the rest uniformly; a held-out split scores the global model.
"""
from __future__ import annotations

import numpy as np


def topic_tokens(*, vocab: int, seq_len: int, count: int, topics: int,
                 rng: np.random.Generator):
    """(tokens [count, seq_len] int32, topic [count] int32)."""
    slice_size = max(64, vocab // (2 * topics))
    starts = rng.integers(0, max(1, vocab - slice_size), topics)
    a = rng.integers(3, 97, topics)
    b = rng.integers(1, slice_size, topics)
    topic = rng.integers(0, topics, count).astype(np.int32)
    toks = np.empty((count, seq_len), np.int32)
    cur = rng.integers(0, slice_size, count)
    noise = rng.random((count, seq_len)) < 0.1
    jumps = rng.integers(0, slice_size, (count, seq_len))
    for s in range(seq_len):
        cur = np.where(noise[:, s], jumps[:, s],
                       (a[topic] * cur + b[topic]) % slice_size)
        toks[:, s] = starts[topic] + cur
    return toks, topic


def label_shards(labels, clients: int, shards: int,
                 rng: np.random.Generator) -> np.ndarray:
    """[clients, n_k] indices: sort by label, cut ``clients * shards``
    equal shards, deal each client ``shards`` of them at random."""
    order = np.argsort(labels, kind="stable")
    n = clients * shards
    cut = order[:(len(order) // n) * n].reshape(n, -1)
    perm = rng.permutation(n)
    return np.stack([np.concatenate([cut[perm[c * shards + i]]
                                     for i in range(shards)])
                     for c in range(clients)])


def distributions(labels_rows, classes: int) -> np.ndarray:
    d = np.stack([np.bincount(r, minlength=classes)
                  for r in np.atleast_2d(labels_rows)]).astype(np.float32)
    return d / np.clip(d.sum(1, keepdims=True), 1, None)


def token_federation(t: dict, vocab: int, rng: np.random.Generator) -> dict:
    """The federated corpus of a training mix ``t`` (its ``data`` block)."""
    d = t["data"]
    toks, topic = topic_tokens(vocab=vocab, seq_len=d["seq_len"] + 1,
                               count=d["sequences"], topics=d["topics"],
                               rng=rng)
    x, y = toks[:, :-1], toks[:, 1:]
    n_test = max(1, int(d["test_fraction"] * len(toks)))
    train_n = len(toks) - n_test
    pool = min(max(t["clients"], int(d["device_share"] * train_n)),
               train_n - 1)
    client_ix = label_shards(topic[:pool], t["clients"], d["shards"], rng)
    rest = np.arange(pool, train_n)
    n0 = min(max(1, int(d["server_fraction"] * pool)), len(rest))
    server_ix = rest[rng.choice(len(rest), n0, replace=False)]
    server_dist = distributions(topic[server_ix], d["topics"])[0]
    return {
        "client_x": x[client_ix], "client_y": y[client_ix],
        "sizes": np.full(t["clients"], client_ix.shape[1], np.float32),
        "client_dists": distributions(topic[client_ix], d["topics"]),
        "server_x": x[server_ix], "server_y": y[server_ix],
        "server_dist": server_dist,
        "test_x": x[train_n:], "test_y": y[train_n:],
    }


def class_images(*, count: int, test: int, shape, classes: int, rank: int,
                 noise: float, rng: np.random.Generator):
    """(train x [count, *shape] f32, train y, test x, test y)."""
    dim = int(np.prod(shape))
    protos = rng.standard_normal((classes, dim), np.float32)
    protos /= np.linalg.norm(protos, axis=1, keepdims=True)
    basis = rng.standard_normal((rank, dim), np.float32)
    basis /= np.linalg.norm(basis, axis=1, keepdims=True)
    coeff = rng.standard_normal((classes, rank), np.float32)

    def make(n):
        y = rng.integers(0, classes, n).astype(np.int32)
        z = rng.standard_normal((n, rank), np.float32) * 0.3
        x = rng.standard_normal((n, dim), np.float32)
        x *= noise
        x += protos[y]
        x += ((coeff[y] + z) @ basis) * 0.5
        return x.reshape(n, *shape), y

    return (*make(count), *make(test))


def image_federation(t: dict, dm: dict, rng: np.random.Generator) -> dict:
    """The paper's Section 4.1 protocol: a device pool label-sharded over
    the clients, the server's share drawn uniformly from the rest of the
    training images, a held-out test split."""
    d = t["data"]
    x, y, test_x, test_y = class_images(
        count=d["train"], test=d["test"], shape=dm["image"],
        classes=dm["classes"], rank=d["rank"], noise=d["noise"], rng=rng)
    pool = d["device_pool"]
    client_ix = label_shards(y[:pool], t["clients"], d["shards"], rng)
    rest = np.arange(pool, len(y))
    n0 = min(max(1, int(d["server_fraction"] * pool)), len(rest))
    server_ix = rest[rng.choice(len(rest), n0, replace=False)]
    return {
        "client_x": x[client_ix], "client_y": y[client_ix],
        "sizes": np.full(t["clients"], client_ix.shape[1], np.float32),
        "client_dists": distributions(y[client_ix], dm["classes"]),
        "server_x": x[server_ix], "server_y": y[server_ix],
        "server_dist": distributions(y[server_ix], dm["classes"])[0],
        "test_x": test_x, "test_y": test_y,
    }


def federation(t: dict, dm: dict, rng: np.random.Generator) -> dict:
    """The federated data of training mix ``t`` for a model of dims
    ``dm``, by the kind its ``data`` block names."""
    kind = t["data"]["kind"]
    if kind == "tokens":
        return token_federation(t, dm["V"], rng)
    if kind == "images":
        return image_federation(t, dm, rng)
    raise ValueError(f"unknown data kind {kind!r}")
