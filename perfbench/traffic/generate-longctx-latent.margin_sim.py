"""The reckoning behind `batch` 8, `check_rows` 2 and `ROUTER_SPREAD` 2.0 of
the cell `deepseek-v3.2-l5-ep16.generate-longctx-latent` (ISSUE 54, made
before any table was read; repeated here so that it is in the repository): a
simulation of THIS router alone. 256 logits N(0, s^2), a selection bias N(0,
0.01^2), sigmoid scores, 8 groups of which the 4 of largest (sum of their
best two biased scores) stay, the top 8 inside them, experts 0-15 held; the
routing margin as `deepseek_sparse_reference.py` defines it (the held
experts' edge and the choice of groups, in the router's logits), the
smallest of 4 layers. Prints, a spread: the share of rows at a margin of
0.02 or more, and the chance that fewer than 2 of 4 and of 8 rows are.

    python3 perfbench/traffic/generate-longctx-latent.margin_sim.py
"""

import math

import numpy as np

E, GROUPS, KEEP, K, HELD, LAYERS, SAFE, ROWS = 256, 8, 4, 8, 16, 4, 0.02, 4000


def margins(spread, rng):
    z = rng.normal(0.0, spread, (ROWS, LAYERS, E))
    s = 1.0 / (1.0 + np.exp(-z))
    by = s + rng.normal(0.0, 0.01, E)
    slopes = s * (1.0 - s)
    grouped = by.reshape(ROWS, LAYERS, GROUPS, E // GROUPS)
    best2 = np.argsort(-grouped, axis=-1)[..., :2]
    score = np.take_along_axis(grouped, best2, -1).sum(-1)
    per = np.take_along_axis(slopes.reshape(grouped.shape), best2, -1).sum(-1)
    order = np.argsort(-score, axis=-1)
    pair = order[..., KEEP - 1:KEEP + 1]
    gap = np.take_along_axis(score, pair, -1)
    group_margin = (gap[..., 0] - gap[..., 1]) / np.take_along_axis(
        per, pair, -1).max(-1)
    stays = np.argsort(order, axis=-1) < KEEP
    limited = np.where(stays[..., None], grouped, -np.inf).reshape(by.shape)
    top = np.argsort(-limited, axis=-1)[..., :K + 1]
    values = np.take_along_axis(limited, top, -1)
    taken = np.zeros(by.shape, bool)
    np.put_along_axis(taken, top[..., :K], True, -1)
    here = np.arange(E) < HELD
    low_held = np.where(taken & here, limited, np.inf).min(-1)
    best_held = np.where(~taken & here, limited, -np.inf).max(-1)
    slope = np.take_along_axis(slopes, top[..., K - 1:], -1).max(-1)
    expert_margin = np.minimum(low_held - values[..., K],
                               values[..., K - 1] - best_held) / slope
    return np.minimum(expert_margin, group_margin).min(-1)


def fewer_than_two(p, n):
    return (1 - p) ** n + n * p * (1 - p) ** (n - 1)


if __name__ == "__main__":
    seeded = 0.02 * math.sqrt(7168)
    for times in (1.0, 2.0, 3.0):
        p = float((margins(times * seeded, np.random.default_rng(54))
                   >= SAFE).mean())
        print(f"spread {times * seeded:.2f} ({times:.0f} x seeded): safe "
              f"{p:.2f}; fewer than 2 of 4 rows {fewer_than_two(p, 4):.2%}, "
              f"of 8 rows {fewer_than_two(p, 8):.4%}")
