"""Token traffic for training cells, made from the seed.

A copy of the program's synthetic generator (``data/pipeline.py``) for
decoder-only models, so that the reference reads its own tokens: uniform
ids with every odd position a fixed function of the one before it, the
targets shifted by one.  Batch ``step`` of a run with ``seed`` is a pure
function of the two.
"""

from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, rows: int, seq_len: int,
          vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens, targets)`` of one global batch, int32 ``(rows, seq_len)``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    tok = rng.integers(0, vocab, (rows, seq_len), dtype=np.int32)
    tok[:, 1::2] = (tok[:, 0::2] * 31 + 7) % vocab
    return tok, np.roll(tok, -1, axis=1)
