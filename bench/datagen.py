"""The benchmark's own data: synthetic MNIST and its federated split.

A copy of the program's generators (``repro.data.synthetic.synthetic_mnist``
and ``repro.data.partition.iid_partition``) with the same recipe: ten fixed
class prototypes of three Gaussian blobs and a stroke, each sample a
prototype shifted by up to 3 pixels, plus Gaussian noise (sigma 0.35) and a
brightness factor in [0.8, 1.2].  It lives here so that a change to the
program's generators cannot move the yardstick, and it is vectorised: the
shifts index a table of the 49 shifted copies of each prototype instead of
rolling every sample in a Python loop.

The training images, their labels and the partition are drawn from one
``numpy.random.Generator`` seeded with the run's seed, so the same seed
gives the same data.  The test set is one fixed set for every seed, as
MNIST's is: the program builds its evaluators with the test images as
constants of the compiled programs, so a test set drawn from the seed would
give every seed programs of its own that no earlier run has cached.
"""
from __future__ import annotations

import numpy as np

IMG = 28
CLASSES = 10
PROTOTYPE_SEED = 1234
TEST_SEED = 4321
SHIFT = 3
NOISE = 0.35


def prototypes() -> np.ndarray:
    """The ten (28, 28) class prototypes, fixed for every run."""
    rng = np.random.RandomState(PROTOTYPE_SEED)
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    protos = []
    for _ in range(CLASSES):
        base = np.zeros((IMG, IMG), np.float32)
        for _ in range(3):
            cy, cx = rng.randint(4, IMG - 4, size=2)
            base += np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                           / (2 * rng.uniform(2, 9)))
        r0, r1 = sorted(rng.randint(2, IMG - 2, size=2))
        base[r0:r1 + 1, rng.randint(2, IMG - 2)] += 1.0
        protos.append(base / base.max())
    return np.stack(protos).astype(np.float32)


def _shifted_table(protos: np.ndarray) -> np.ndarray:
    """(classes, 7, 7, 28, 28): every prototype rolled by (dy, dx) in
    [-3, 3]^2, the same wrap-around shift as ``np.roll``."""
    span = 2 * SHIFT + 1
    table = np.empty((len(protos), span, span, IMG, IMG), np.float32)
    for a, dy in enumerate(range(-SHIFT, SHIFT + 1)):
        for b, dx in enumerate(range(-SHIFT, SHIFT + 1)):
            table[:, a, b] = np.roll(np.roll(protos, dy, axis=1), dx, axis=2)
    return table


def images(rng: np.random.Generator, n: int, table: np.ndarray):
    """``n`` labelled samples: (images (n, 28, 28) float32, labels (n,))."""
    labels = rng.integers(0, CLASSES, size=n).astype(np.int32)
    dy = rng.integers(0, 2 * SHIFT + 1, size=n)
    dx = rng.integers(0, 2 * SHIFT + 1, size=n)
    x = table[labels, dy, dx]
    x += rng.standard_normal(x.shape, dtype=np.float32) * np.float32(NOISE)
    x *= rng.uniform(0.8, 1.2, size=(n, 1, 1)).astype(np.float32)
    return x, labels


def iid_split(rng: np.random.Generator, x: np.ndarray, y: np.ndarray,
              num_clients: int):
    """Equal IID split: a permutation of the training set cut into
    ``num_clients`` shards of ``len(x) // num_clients`` samples (the tail is
    dropped).  Returns (images (N, M, 28, 28), labels (N, M), mask (N, M),
    counts (N,)); every mask entry is 1."""
    spc = len(x) // num_clients
    order = rng.permutation(len(x))[:num_clients * spc]
    xs = x[order].reshape(num_clients, spc, *x.shape[1:])
    ys = y[order].reshape(num_clients, spc)
    mask = np.ones((num_clients, spc), np.float32)
    counts = np.full(num_clients, spc, np.int32)
    return xs, ys, mask, counts


def make(seed: int, data_cfg: dict, num_clients: int) -> dict:
    """The whole data set of one run: the training data from its seed, the
    fixed test set."""
    if data_cfg["partition"] != "iid":
        raise ValueError(f"unknown partition {data_cfg['partition']!r}")
    rng = np.random.default_rng(seed)
    table = _shifted_table(prototypes())
    xtr, ytr = images(rng, data_cfg["train_samples"], table)
    xte, yte = images(np.random.default_rng(TEST_SEED),
                      data_cfg["test_samples"], table)
    xs, ys, mask, counts = iid_split(rng, xtr, ytr, num_clients)
    return {"images": xs, "labels": ys, "mask": mask, "counts": counts,
            "test_images": xte, "test_labels": yte}
