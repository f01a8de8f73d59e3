"""Seeds of a run, derived from ``--seed``.

``--seed`` may exceed what 32 signed bits hold; each named stream gets its
own 31-bit seed from one ``SeedSequence``, so the same ``--seed`` always
gives the same data, weights and draws.
"""

from __future__ import annotations

import numpy as np


def derive(seed: int, names) -> dict:
    names = tuple(names)
    state = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(s) & 0x7FFFFFFF for n, s in zip(names, state)}
