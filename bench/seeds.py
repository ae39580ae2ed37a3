"""Seeds of any size made into random-number state."""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two uint32 words from a seed of any size (``jax.random.key`` keeps
    only the low 32 bits of a large seed)."""
    return np.random.SeedSequence([int(seed), int(salt)]).generate_state(
        2, np.uint32)
