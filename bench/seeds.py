"""Keys drawn from a run's ``--seed``, shared by the program's weights and
the reference's copy of them, so both draw the same numbers."""
from __future__ import annotations

import jax


def weights_key(seed: int):
    """``PRNGKey`` keeps only the low 32 bits of a seed, so the high bits
    are folded in: seeds that differ above bit 31 give different weights."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)
