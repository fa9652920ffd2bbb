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
"""
from __future__ import annotations

import dataclasses
from typing import List

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
    clients: List[dict]      # {"images": (nb, B, H, W, C), "labels": (nb, B)}
    test: dict               # {"images": (n, H, W, C), "labels": (n,)}
    server_seed: int

    @property
    def n_batches(self) -> List[int]:
        return [c["labels"].shape[0] for c in self.clients]


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
