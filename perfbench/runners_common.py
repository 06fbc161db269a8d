"""What the serving kinds of cell share: the first-token oracle."""

from __future__ import annotations

import numpy as np

# Two correct bf16 programs order their sums differently, and 36 layers of
# bf16 rounding reach the logits: on the chip the engine's logits and a
# float32 forward differ by up to ~8 bf16 steps (0.03 relative, PERF.md PR 21
# finding 9), and the top two of 151936 seeded logits sit that close about
# one time in eight. So a first token must be the reference's argmax or lie
# within 2^-4 of it (twice the measured noise); a wrong token (a cache,
# position or weight-layout fault) lies a whole logit spread away.
TIE_TOL = 2.0 ** -4


def tie_gap(row: np.ndarray, got: int) -> float:
    """0 if `got` is the argmax of the reference's logits, else how far
    below it, relative to the larger of the two."""
    want = int(np.argmax(row))
    if got == want:
        return 0.0
    return abs(float(row[got]) - float(row[want])) / max(
        abs(float(row[got])), abs(float(row[want])), 1e-6)
