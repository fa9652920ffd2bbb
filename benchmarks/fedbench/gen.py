"""Traffic generator: CIFAR-shaped images and their split over clients.

One general generator reads a workload's ``traffic`` parameters.  It is
a NumPy copy of the program's ``make_cifar_like`` (one smooth random
template per class, images = template + pixel noise, times a random
brightness) and of the IID and Dirichlet label-skew partitioners
(Hsu et al., arXiv:1909.06335), built so that every seed gives the same
shapes and the same work:

* ``--seed`` draws the templates, the images, the order of each
  client's examples and the test set, and the server seed (initial
  weights and client keys);
* the number of examples of each class on each client comes from the
  cell's own ``partition_seed`` alone, so the client-size profile, the
  padded batch count and the compiled programs are the same for every
  seed.

Everything is made on the host in bulk, as a real data set arrives:
no device program compiles for it.

A workload's ``traffic`` names its kind: absent (or ``"images"``) it is
the image traffic above (:class:`Traffic`); ``"kind": "tokens"`` is
token traffic for a language model (:class:`TokenTraffic`): packed
documents of token ids from a seeded order-1 source per topic, each
client mixing the topics by a Dirichlet draw (:func:`make_tokens`).
"""
from __future__ import annotations

import dataclasses
from typing import List, Union

import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    n_train: int
    n_test: int
    n_clients: int
    batch_size: int
    partition: str = "iid"            # "iid" | "dirichlet"
    dirichlet_alpha: float = 0.5
    partition_seed: int = 1
    num_classes: int = 10
    image_size: int = 32
    channels: int = 3
    noise: float = 0.35
    smooth_passes: int = 3

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        return cls(**d)


@dataclasses.dataclass
class Dataset:
    """Per-client batched shards and the test set, as host arrays."""
    clients: List[dict]      # arrays with leading (n_batches, batch) axes
    test: dict               # arrays with a leading example axis
    server_seed: int

    @property
    def n_batches(self) -> List[int]:
        return [c[min(c)].shape[0] for c in self.clients]


def seed_streams(seed: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators from one seed of any size."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(n)]


def server_seed(seed: int) -> int:
    """A 31-bit server seed: ``jax.random.PRNGKey`` keeps only the low
    32 bits of a larger integer, so large seeds are hashed down here."""
    ss = np.random.SeedSequence([int(seed), 0x5EED])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def class_counts(t: Traffic) -> np.ndarray:
    """``(n_clients, num_classes)`` example counts, from
    ``partition_seed`` only.  Every class holds ``n_train / num_classes``
    examples, as in CIFAR-10."""
    per_class = t.n_train // t.num_classes
    if t.partition == "iid":
        per_client = t.n_train // t.n_clients
        # an even split of a balanced, shuffled set: spread each
        # client's examples over the classes as evenly as possible
        counts = np.zeros((t.n_clients, t.num_classes), np.int64)
        flat = np.arange(per_client * t.n_clients) % t.num_classes
        for k in range(t.n_clients):
            counts[k] = np.bincount(flat[k * per_client:(k + 1) * per_client],
                                    minlength=t.num_classes)
        return counts
    if t.partition != "dirichlet":
        raise ValueError(f"unknown partition {t.partition!r}")
    rng = np.random.default_rng(int(t.partition_seed))
    counts = np.zeros((t.n_clients, t.num_classes), np.int64)
    for c in range(t.num_classes):
        props = rng.dirichlet([t.dirichlet_alpha] * t.n_clients)
        cuts = (np.cumsum(props) * per_class).astype(int)[:-1]
        counts[:, c] = np.diff(np.concatenate([[0], cuts, [per_class]]))
    return counts


def _smooth(rng: np.random.Generator, shape, passes: int) -> np.ndarray:
    x = rng.standard_normal(shape, dtype=np.float32)
    for _ in range(passes):
        x = (x + np.roll(x, 1, 0) + np.roll(x, -1, 0)
             + np.roll(x, 1, 1) + np.roll(x, -1, 1)) / 5.0
    return x


def _images(rng: np.random.Generator, templates: np.ndarray,
            labels: np.ndarray, noise: float) -> np.ndarray:
    out = rng.standard_normal((labels.shape[0],) + templates.shape[1:],
                              dtype=np.float32)
    out *= np.float32(noise)
    out += templates[labels]
    bright = 1.0 + 0.1 * rng.standard_normal((labels.shape[0], 1, 1, 1),
                                             dtype=np.float32)
    out *= bright
    return out


def make_dataset(t: Traffic, seed: int) -> Dataset:
    r_tpl, r_train, r_order, r_test = seed_streams(seed, 4)
    shape = (t.image_size, t.image_size, t.channels)
    templates = np.stack([_smooth(r_tpl, shape, t.smooth_passes)
                          for _ in range(t.num_classes)])
    templates /= templates.std(axis=(1, 2, 3), keepdims=True) + 1e-6

    clients = []
    for row in class_counts(t):
        labels = np.repeat(np.arange(t.num_classes, dtype=np.int32), row)
        r_order.shuffle(labels)
        nb = labels.shape[0] // t.batch_size
        if nb == 0:
            raise ValueError("a client holds less than one batch")
        labels = labels[:nb * t.batch_size]
        images = _images(r_train, templates, labels, t.noise)
        clients.append({
            "images": images.reshape((nb, t.batch_size) + shape),
            "labels": labels.reshape(nb, t.batch_size)})

    test_labels = np.arange(t.n_test, dtype=np.int32) % t.num_classes
    r_test.shuffle(test_labels)
    test = {"images": _images(r_test, templates, test_labels, t.noise),
            "labels": test_labels}
    return Dataset(clients=clients, test=test, server_seed=server_seed(seed))


# ------------------------------------------------------------ tokens --
@dataclasses.dataclass(frozen=True)
class TokenTraffic:
    """Token traffic for a language model.  Every client holds
    ``n_batches`` batches of ``batch_size`` packed sequences of
    ``seq_len + 1`` token ids (inputs and next-token targets); the test
    set holds ``n_test`` such sequences."""
    n_clients: int
    batch_size: int
    n_batches: int
    n_test: int
    seq_len: int
    vocab_size: int
    topics: int
    dirichlet_alpha: float       # each client's mix of the topics
    doc_len_median: float        # documents: lognormal lengths
    doc_len_sigma: float
    p_successor: float           # next token from the successor table
    successors: int              # successors per token and topic
    zipf_s: float                # else Zipf(zipf_s) over the topic's ranks
    partition_seed: int = 1


KINDS = {"images": Traffic, "tokens": TokenTraffic}


def traffic(d: dict) -> Union[Traffic, TokenTraffic]:
    """A workload's ``traffic`` as its kind's parameters."""
    d = dict(d)
    kind = d.pop("kind", "images")
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return KINDS[kind](**d)


def generate(t: Union[Traffic, TokenTraffic], seed: int) -> Dataset:
    if isinstance(t, TokenTraffic):
        return make_tokens(t, seed)
    return make_dataset(t, seed)


def topic_mix(t: TokenTraffic) -> np.ndarray:
    """``(n_clients, topics)`` topic shares, from ``partition_seed``
    only, as the image traffic's class counts."""
    rng = np.random.default_rng(int(t.partition_seed))
    return rng.dirichlet([t.dirichlet_alpha] * t.topics, size=t.n_clients)


def doc_lengths(rng: np.random.Generator, rows: int, length: int,
                median: float, sigma: float) -> np.ndarray:
    """``(rows, k)`` lognormal document lengths of at least 1 token,
    enough columns that every row's documents fill ``length`` tokens."""
    k = 2 + int(np.ceil(4 * length / median))

    def draw():
        x = rng.lognormal(np.log(median), sigma, (rows, k))
        return np.maximum(1, np.rint(x)).astype(np.int64)
    out = draw()
    while (out.sum(1) < length).any():
        out = np.concatenate([out, draw()], 1)
    return out


def pack(lengths: np.ndarray, length: int) -> np.ndarray:
    """``(rows, length)`` document ids of each position: row ``i``
    holds its documents one after the other, the last one cut at the
    end of the row."""
    ends = np.cumsum(lengths, 1)
    pos = np.arange(length)
    return np.stack([np.searchsorted(e, pos, side="right")
                     for e in ends]).astype(np.int32)


def _sequences(rng: np.random.Generator, mix: np.ndarray, t: TokenTraffic,
               perm: np.ndarray, succ: np.ndarray, cdf: np.ndarray):
    """``mix.shape[0]`` packed sequences, row ``i`` with documents of
    topics drawn from ``mix[i]``: (tokens, segments)."""
    rows, length = mix.shape[0], t.seq_len + 1
    segments = pack(doc_lengths(rng, rows, length, t.doc_len_median,
                                t.doc_len_sigma), length)
    n_docs = int(segments.max()) + 1
    u = rng.random((rows, n_docs))
    doc_topic = (u[:, :, None] > np.cumsum(mix, 1)[:, None, :]).sum(-1)
    doc_topic = np.minimum(doc_topic, t.topics - 1)
    topic = np.take_along_axis(doc_topic, segments, 1)
    start = np.ones((rows, length), bool)
    start[:, 1:] = segments[:, 1:] != segments[:, :-1]

    rank = np.minimum(np.searchsorted(cdf, rng.random((rows, length)),
                                      side="right"), t.vocab_size - 1)
    fresh = perm[topic, rank]
    follow = (rng.random((rows, length)) < t.p_successor) & ~start
    pick = rng.integers(0, t.successors, (rows, length))
    tokens = np.empty((rows, length), np.int32)
    tokens[:, 0] = fresh[:, 0]
    for p in range(1, length):
        nxt = succ[topic[:, p], tokens[:, p - 1], pick[:, p]]
        tokens[:, p] = np.where(follow[:, p], nxt, fresh[:, p])
    return tokens, segments


def make_tokens(t: TokenTraffic, seed: int) -> Dataset:
    """Per topic, a random permutation of the vocabulary gives the Zipf
    rank of each token and a table gives each token's ``successors``;
    a token follows the one before it from that table with probability
    ``p_successor`` (never across a document boundary), else it is a
    fresh Zipf draw.  The test set mixes the topics evenly."""
    r_tab, r_train, r_test = seed_streams(seed, 3)
    v = t.vocab_size
    perm = np.stack([r_tab.permutation(v)
                     for _ in range(t.topics)]).astype(np.int32)
    succ = r_tab.integers(0, v, (t.topics, v, t.successors), dtype=np.int32)
    weights = np.arange(1, v + 1, dtype=np.float64) ** -t.zipf_s
    cdf = np.cumsum(weights) / weights.sum()

    per_client = t.n_batches * t.batch_size
    mix = np.repeat(topic_mix(t), per_client, axis=0)
    tokens, segments = _sequences(r_train, mix, t, perm, succ, cdf)
    shape = (t.n_clients, t.n_batches, t.batch_size, t.seq_len + 1)
    tokens, segments = tokens.reshape(shape), segments.reshape(shape)
    clients = [{"segments": segments[k], "tokens": tokens[k]}
               for k in range(t.n_clients)]
    even = np.full((t.n_test, t.topics), 1.0 / t.topics)
    tt, ts = _sequences(r_test, even, t, perm, succ, cdf)
    return Dataset(clients=clients, test={"segments": ts, "tokens": tt},
                   server_seed=server_seed(seed))
