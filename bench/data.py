"""The benchmark's stream of sensor readings, made from the seed.

A copy of the repository's ``chem`` stand-in (`gaussian_mixtures` with
the gas-sensor shape: d = 16, 8 clusters, overlap 0.30, 10 % noise), kept
here so that the yardstick cannot move when the program's own generator
does.  `Stream` makes the readings lazily in chunks: its first chunk is
exactly ``gaussian_mixtures(chunk, seed=seed, ...)``, and every later
chunk draws from the same mixture (same centres, weights, scales and
noise box), so the stream never runs out and never drifts.
"""

from __future__ import annotations

import numpy as np

# the deployments' stream shapes (copied from the program's stand-ins)
SPECS = {
    "chem": dict(d=16, k=8, overlap=0.30, noise_frac=0.10),
}


def gaussian_mixtures(n, d=10, k=20, overlap=0.10, noise_frac=0.0, seed=0):
    """MixSim-flavoured Gaussian mixtures; returns (X (n, d), labels (n,))."""
    X, y, _ = _mixture(np.random.default_rng(seed), n, d, k, overlap, noise_frac)
    return X, y


def _mixture(rng, n, d, k, overlap, noise_frac, frozen=None):
    """One draw of n readings.  Without ``frozen`` the mixture itself is
    drawn first, in the order the original generator draws it; with it
    (centres, weights, scales, noise box) only the readings are drawn."""
    if frozen is None:
        delta = np.sqrt(-8.0 * np.log(max(overlap, 1e-6)))
        centers = np.zeros((k, d))
        placed = 0
        while placed < k:
            c = rng.uniform(-delta * k ** (1.0 / d), delta * k ** (1.0 / d), size=d)
            if placed == 0 or np.linalg.norm(centers[:placed] - c, axis=1).min() >= delta:
                centers[placed] = c
                placed += 1
        weights = rng.dirichlet(np.full(k, 5.0))
        scales = None
    else:
        centers, weights, scales, box = frozen
    counts = rng.multinomial(n, weights)
    X = np.empty((n, d))
    y = np.empty(n, dtype=np.int64)
    drawn = []
    at = 0
    for i, c in enumerate(counts):
        scale = rng.uniform(0.7, 1.3) if scales is None else scales[i]
        drawn.append(scale)
        X[at : at + c] = rng.normal(loc=centers[i], scale=scale, size=(c, d))
        y[at : at + c] = i
        at += c
    if frozen is None:
        box = (X.min(axis=0), X.max(axis=0))
    n_noise = int(noise_frac * n)
    if n_noise:
        idx = rng.choice(n, size=n_noise, replace=False)
        X[idx] = rng.uniform(box[0], box[1], size=(n_noise, d))
        y[idx] = -1
    perm = rng.permutation(n)
    return X[perm], y[perm], (centers, weights, np.asarray(drawn), box)


class Stream:
    """Endless seeded readings of one deployment, made ``chunk`` at a time.

    ``take(m)`` returns the next m rows and their stream positions; rows
    made so far stay addressable through ``rows(positions)``, which the
    correctness check uses to rebuild the window."""

    def __init__(self, spec: str, seed: int, chunk: int = 65536):
        self.spec_name, self.spec = spec, dict(SPECS[spec])
        self.seed = int(seed)
        self.chunk = int(chunk)
        self._rng = np.random.default_rng(self.seed)
        self._frozen = None
        self._chunks: list[np.ndarray] = []
        self.cursor = 0

    @property
    def dim(self) -> int:
        return int(self.spec["d"])

    def _grow(self):
        X, _, frozen = _mixture(self._rng, self.chunk, frozen=self._frozen, **self.spec)
        if self._frozen is None:
            self._frozen = frozen
        self._chunks.append(X)

    def fork(self, tag: int) -> "Stream":
        """A stream of further readings of the same mixture, drawn apart
        from this one's (tagged by ``tag``)."""
        self.ensure(1)
        other = Stream(self.spec_name, self.seed, self.chunk)
        other._rng = np.random.default_rng([self.seed, int(tag)])
        other._frozen = self._frozen
        return other

    def made(self) -> int:
        return len(self._chunks) * self.chunk

    def ensure(self, upto: int):
        while self.made() < upto:
            self._grow()

    def rows(self, pos) -> np.ndarray:
        pos = np.asarray(pos, dtype=np.int64)
        self.ensure(int(pos.max(initial=-1)) + 1)
        c, off = np.divmod(pos, self.chunk)
        out = np.empty((pos.size, self.dim))
        for ci in np.unique(c):
            sel = c == ci
            out[sel] = self._chunks[int(ci)][off[sel]]
        return out

    def take(self, m: int):
        pos = np.arange(self.cursor, self.cursor + m, dtype=np.int64)
        self.cursor += m
        return self.rows(pos), pos
