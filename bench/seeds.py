"""Every random stream of a run, derived from ``--seed``."""
from __future__ import annotations

import zlib

import numpy as np


def derive(seed: int, *tags) -> int:
    """A 31-bit seed for the stream named by ``tags`` (strings or
    integers) of run ``seed``: the same seed and tags give the same
    value, any whole ``seed`` is accepted."""
    words = [int(seed) % 2 ** 64]
    for t in tags:
        words.append(zlib.crc32(t.encode()) if isinstance(t, str)
                     else int(t) % 2 ** 64)
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)
