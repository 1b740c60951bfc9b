"""What ``correct`` is decided on: a seeded sample of the answers served
in the window, kept as they came back."""
from __future__ import annotations

from typing import Any, List

import numpy as np


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length
    (Algorithm R), drawn from the seed's own generator."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng = k, rng
        self.items: List[Any] = []
        self.seen = 0

    def put(self, slot: int, item) -> None:
        self.items[slot] = item

    def offer(self, n: int) -> List[tuple]:
        """(position in the batch, slot) for the next ``n`` items of the
        stream: item i takes a slot with probability k / (i + 1), the
        slot drawn uniformly; one call to the generator per batch."""
        first = self.seen
        self.seen += n
        keep = []
        for j in range(min(n, max(0, self.k - first))):
            self.items.append(None)
            keep.append((j, first + j))
        rest = np.arange(len(keep), n)
        if len(rest):
            draws = self.rng.integers(0, first + rest + 1)
            keep += [(int(j), int(d)) for j, d in zip(rest, draws)
                     if d < self.k]
        return keep
