"""The reckoning behind `batch` 8, `check_rows` 2 and `ROUTER_SPREAD` of the
cell `openpangu-ultra-l5-ep16.generate-longctx-dense` (ISSUE 58: ISSUE 54's
simulation repeated for THIS router, before any table was read): a
simulation of the router alone. 256 logits N(0, s^2), sigmoid scores, NO
groups and NO selection bias: the 8 largest of all 256 at once, experts 0-15
held; the routing margin as `openpangu_reference.py` defines it (the held
experts' edge, in the router's logits), the smallest of 4 expert layers.
Prints, a spread: the share of rows at a margin of 0.02 or more, and the
chance that fewer than 2 of 4 and of 8 rows are.

    python3 perfbench/traffic/generate-longctx-dense.margin_sim.py
"""

import math

import numpy as np

E, K, HELD, LAYERS, SAFE, ROWS = 256, 8, 16, 4, 0.02, 4000


def margins(spread, rng):
    z = rng.normal(0.0, spread, (ROWS, LAYERS, E))
    s = 1.0 / (1.0 + np.exp(-z))
    slopes = s * (1.0 - s)
    top = np.argsort(-s, axis=-1)[..., :K + 1]
    values = np.take_along_axis(s, top, -1)
    taken = np.zeros(s.shape, bool)
    np.put_along_axis(taken, top[..., :K], True, -1)
    here = np.arange(E) < HELD
    low_held = np.where(taken & here, s, np.inf).min(-1)
    best_held = np.where(~taken & here, s, -np.inf).max(-1)
    slope = np.take_along_axis(slopes, top[..., K - 1:], -1).max(-1)
    return (np.minimum(low_held - values[..., K],
                       values[..., K - 1] - best_held) / slope).min(-1)


def fewer_than_two(p, n):
    return (1 - p) ** n + n * p * (1 - p) ** (n - 1)


if __name__ == "__main__":
    seeded = 0.02 * math.sqrt(7680)
    for times in (1.0, 2.0, 3.0):
        p = float((margins(times * seeded, np.random.default_rng(58))
                   >= SAFE).mean())
        print(f"spread {times * seeded:.2f} ({times:.0f} x seeded): safe "
              f"{p:.2f}; fewer than 2 of 4 rows {fewer_than_two(p, 4):.2%}, "
              f"of 8 rows {fewer_than_two(p, 8):.4%}")
