"""The reckoning behind `ROUTER_SPREAD` 8.0 and `check_rows` 2 of the cell
`qwen3-next-80b-l12-ep8.generate-longctx-linear` (ISSUE 64: ISSUE 60's
simulation repeated for THIS router, before any table was read): a
simulation of the router alone. 512 logits N(0, s^2), a softmax (which keeps
their order: the choice is the 10 largest logits), no selection bias, no
groups, experts 0-63 held; the routing margin as `qwen3_next_reference.py`
defines it (the held experts' edge, in the router's logits), the smallest of
12 layers. Prints, a spread: the share of rows at a margin of 0.02 or more,
and the chance that fewer than 2 of 8 rows are (`check_sample` then ends the
run).

What it reckons is ROWS TO JUDGE. It knows nothing of rounding: a wider router
widens the bf16 noise of its logits with their margins
(`trinity-mini-l16-ep8.json`'s `assumed.routed_expert_damp` has the readings).
What keeps a flip from deciding `correct` is
`qwen3_next_adapter.ROUTED_EXPERT_DAMP`.

    python3 perfbench/traffic/generate-longctx-linear.margin_sim.py
"""

import math

import numpy as np

E, K, HELD, LAYERS, SAFE, ROWS = 512, 10, 64, 12, 0.02, 4000


def margins(spread, rng):
    z = rng.normal(0.0, spread, (ROWS, LAYERS, E)).astype(np.float32)
    top = np.argsort(-z, axis=-1)[..., :K + 1]
    values = np.take_along_axis(z, top, -1)
    taken = np.zeros(z.shape, bool)
    np.put_along_axis(taken, top[..., :K], True, -1)
    here = np.arange(E) < HELD
    low_held = np.where(taken & here, z, np.inf).min(-1)
    best_held = np.where(~taken & here, z, -np.inf).max(-1)
    return np.minimum(low_held - values[..., K],
                      values[..., K - 1] - best_held).min(-1)


def fewer_than(k, p, n):
    return sum(math.comb(n, i) * p ** i * (1 - p) ** (n - i)
               for i in range(k))


if __name__ == "__main__":
    seeded = 0.02 * math.sqrt(2048)
    for times in (1.0, 2.0, 4.0, 8.0):
        p = float((margins(times * seeded, np.random.default_rng(64))
                   >= SAFE).mean())
        print(f"spread {times * seeded:.2f} ({times:.0f} x seeded): safe "
              f"{p:.2f}; fewer than 2 of 8 rows {fewer_than(2, p, 8):.2e}")
