"""The reckoning behind `ROUTER_SPREAD` 4.0 and `check_rows` 4 of the cell
`trinity-mini-l16-ep8.generate-agent-8k` (ISSUE 60: ISSUE 58's simulation
repeated for THIS router, before any table was read): a simulation of the
router alone. 128 logits N(0, s^2), sigmoid scores, a selection bias
normal(0.01) in the CHOICE, no groups: the 8 largest of all 128 biased
scores at once, experts 0-15 held; the routing margin as `afmoe_reference.py`
defines it (the held experts' edge, in the router's logits), the smallest of
14 expert layers. Prints, a spread: the share of rows at a margin of 0.02 or
more, and the chance that fewer than 4 of 32 rows are.

What it reckons is ROWS TO JUDGE. It knows nothing of rounding: a wider router
widens the bf16 noise of its logits with their margins, so a row at 0.02 is no
safer at 4.0 x than a row at 0.005 as seeded (on the chip 18% of the rows it
calls safe at 4.0 x flip an expert in some layer). What keeps a flip from
deciding `correct` is `afmoe_adapter.ROUTED_EXPERT_DAMP` (the configuration's
`assumed.routed_expert_damp`).

    python3 perfbench/traffic/generate-agent-8k.margin_sim.py
"""

import math

import numpy as np

E, K, HELD, LAYERS, SAFE, ROWS, BIAS = 128, 8, 16, 14, 0.02, 4000, 0.01


def margins(spread, rng):
    z = rng.normal(0.0, spread, (ROWS, LAYERS, E))
    s = 1.0 / (1.0 + np.exp(-z))
    c = s + rng.normal(0.0, BIAS, (1, LAYERS, E))     # a layer's own bias
    slopes = s * (1.0 - s)
    top = np.argsort(-c, axis=-1)[..., :K + 1]
    values = np.take_along_axis(c, top, -1)
    taken = np.zeros(s.shape, bool)
    np.put_along_axis(taken, top[..., :K], True, -1)
    here = np.arange(E) < HELD
    low_held = np.where(taken & here, c, np.inf).min(-1)
    best_held = np.where(~taken & here, c, -np.inf).max(-1)
    slope = np.take_along_axis(slopes, top[..., K - 1:], -1).max(-1)
    return (np.minimum(low_held - values[..., K],
                       values[..., K - 1] - best_held) / slope).min(-1)


def fewer_than(k, p, n):
    return sum(math.comb(n, i) * p ** i * (1 - p) ** (n - i)
               for i in range(k))


if __name__ == "__main__":
    seeded = 0.02 * math.sqrt(2048)
    for times in (1.0, 2.0, 4.0):
        p = float((margins(times * seeded, np.random.default_rng(60))
                   >= SAFE).mean())
        print(f"spread {times * seeded:.2f} ({times:.0f} x seeded): safe "
              f"{p:.2f}; fewer than 4 of 32 rows {fewer_than(4, p, 32):.2e}")
