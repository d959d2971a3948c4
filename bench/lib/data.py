"""The benchmark's own data: seeded synthetic digit gratings, split over
the users, wrapped in the system's dataset type.

The images follow ``repro.data.mixtures.digits_like_mixture`` (a class is
an oriented grating under a Gaussian envelope, plus noise) and the splits
follow ``repro.data.federated``; both are copied here so that the inputs
are the benchmark's, not the program's.  A user's sampler draws
``rng.integers(0, len(shard), n)`` rows of its shard from the generator
the session passes in, exactly as the system's own samplers do, so the
reference can replay every batch from the shards and the session's seed.
"""

from __future__ import annotations

import numpy as np


def grating(cls: int, size: int) -> np.ndarray:
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float64) / size - 0.5
    theta = np.pi * cls / 10.0
    freq = 3.0 + (cls % 5)
    wave = np.sin(2 * np.pi * freq * (xx * np.cos(theta) + yy * np.sin(theta)))
    img = wave * np.exp(-((xx ** 2 + yy ** 2) / 0.18))
    return (img / np.abs(img).max()).astype(np.float32)


def digit_images(size: int, per_class: int, seed: int):
    """(10 * per_class, size, size) images and their labels."""
    rng = np.random.default_rng(seed)
    data, labels = [], []
    for c in range(10):
        noise = rng.normal(0, 0.15, (per_class, size, size)).astype(np.float32)
        data.append(np.clip(grating(c, size)[None] + noise, -1, 1))
        labels.append(np.full(per_class, c))
    return np.concatenate(data), np.concatenate(labels)


def dirichlet_shards(data, labels, users: int, alpha: float, seed: int):
    """Label-skew split: per class, user shares ~ Dirichlet(alpha); an
    empty shard takes one sample from the largest."""
    rng = np.random.default_rng(seed)
    per_user = [[] for _ in range(users)]
    for c in np.unique(labels):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        cuts = (np.cumsum(rng.dirichlet(np.full(users, alpha)))[:-1]
                * len(idx)).astype(np.int64)
        for u, part in enumerate(np.split(idx, cuts)):
            per_user[u].append(part)
    owned = [np.concatenate(p) for p in per_user]
    for u in range(users):
        while len(owned[u]) == 0:
            donor = int(np.argmax([len(o) for o in owned]))
            owned[u], owned[donor] = owned[donor][-1:], owned[donor][:-1]
    return [data[np.sort(o)] for o in owned]


def class_shards(data, labels, users: int):
    """Cross-silo split: the ten classes in ``users`` contiguous groups."""
    groups = np.array_split(np.arange(10), users)
    return [data[np.isin(labels, g)] for g in groups]


def make_shards(spec: dict, sample_shape: tuple, users: int, seed: int):
    """Per-user shards for a traffic file's ``data`` block."""
    data, labels = digit_images(spec["image_size"], spec["per_class"],
                                seed=seed)
    data = data.reshape((len(data),) + tuple(sample_shape))
    if spec["partition"] == "dirichlet":
        return dirichlet_shards(data, labels, users, spec["alpha"],
                                seed=seed + 1)
    if spec["partition"] == "class_split":
        return class_shards(data, labels, users)
    raise ValueError(f"unknown partition {spec['partition']!r}")


def dataset(shards, span=None):
    """The system's ``FederatedDataset`` over the benchmark's shards;
    ``span`` (a :class:`bench.lib.trace.Spans`) marks each batch draw."""
    from repro.data.federated import FederatedDataset

    def sampler(shard):
        def sample(rng, n):
            return shard[rng.integers(0, len(shard), size=n)]
        return sample if span is None else span.wrap("stage.user_batch",
                                                     sample)

    alldata = np.concatenate(shards)
    return FederatedDataset(
        samplers=[sampler(s) for s in shards],
        union_sampler=lambda rng, n: alldata[rng.integers(0, len(alldata),
                                                          size=n)],
        meta={"shard_sizes": [len(s) for s in shards]})


def replay_batches(shards, seed: int, schedule, batch: int) -> np.ndarray:
    """(rounds, C, B, ...) real batches the session draws for ``schedule``
    from ``np.random.default_rng(seed)``, round by round, member by
    member."""
    rng = np.random.default_rng(seed)
    return np.stack([np.stack([
        shards[u][rng.integers(0, len(shards[u]), size=batch)]
        for u in row]) for row in schedule])
