"""The grouped GEMM as a decode step's held expert layer calls it
(`grouped_gemm.held_tiling` at a row tile of 16: the whole contraction in one
K tile), against the float32 `jax.numpy` product, and the rule itself by
shape. The forms `tools/gmm_decode_tiles.py` sweeps on the chip are cases
here: today's K tiles, the whole K at a narrower N tile, groups padded to
whole row tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import held_row_tile
from deepspeed_tpu.ops.pallas import grouped_gemm as gg

TM = gg.DECODE_ROW_TILE
ROWS, EXPERTS = 64, 8
# group sizes over the 64 rows of a call, contiguous from row 0
SIZES = {"straddle": (5, 13, 7, 20, 3, 9, 2, 5),
         "empty": (0, 10, 0, 0, 25, 7, 0, 6),
         "rows_after": (3,) * 8,
         "one_expert": (0, 0, 0, 40, 0, 0, 0, 0)}
# (K, N): Nemotron's 2,688 / 1,856 scaled by 8 (remainders under 128-wide
# tiles), and widths with none
WIDTHS = {"remainder": (336, 232), "whole": (256, 128)}
FORMS = ("rule", "k_tiles", "whole_k_narrow", "padded")
# a family's decode and prefill calls: tokens x top k of either, the
# router's experts, hidden, expert width
FAMILIES = {"nemotron_h": (64 * 6, 8192 * 6, 128, 2688, 1856),
            "ling": (128 * 8, 8192 * 8, 512, 2560, 768),
            "afmoe": (32 * 8, 16384 * 8, 128, 2048, 1024),
            "qwen3_next": (8 * 10, 2048 * 10, 512, 2048, 512),
            "keye": (8 * 8, 2048 * 8, 128, 2048, 768),
            "deepseek": (8 * 8, 2048 * 8, 256, 7168, 2048),
            "openpangu": (8 * 8, 2048 * 8, 256, 7680, 2048)}


def _product(kind, widths, form):
    sizes = np.asarray(SIZES[kind], np.int32)
    k, n = WIDTHS[widths]
    kl, kr = jax.random.split(jax.random.PRNGKey(66))
    lhs = jax.random.normal(kl, (ROWS, k), jnp.float32).astype(jnp.bfloat16)
    rhs = (jax.random.normal(kr, (EXPERTS, k, n), jnp.float32)
           * k ** -0.5).astype(jnp.bfloat16)
    held = int(sizes.sum())
    of_row = np.repeat(np.arange(EXPERTS), sizes)
    want = jnp.einsum("mk,mkn->mn", lhs[:held].astype(jnp.float32),
                      rhs.astype(jnp.float32)[of_row],
                      precision=jax.lax.Precision.HIGHEST)
    tiling = {"rule": gg.held_tiling(TM, k, n),
              "whole_k_narrow": (TM, k, 128)}.get(form, (TM, 128, 128))
    if form == "rule":
        assert tiling == (TM, k, n)
    if form == "padded":
        # each group from a row tile's boundary: a tile is one expert's alone
        padded = -(-sizes // TM) * TM
        at = np.concatenate([np.arange(s) + o for s, o in zip(
            sizes, np.cumsum(padded) - padded)]).astype(np.int32)
        rows = jnp.zeros((int(padded.sum()), k), lhs.dtype).at[at].set(
            lhs[:held])
        got = gg.grouped_gemm(rows, rhs, jnp.asarray(padded), tiling)[at]
    else:
        got = gg.grouped_gemm(lhs, rhs, jnp.asarray(sizes), tiling)[:held]
    # rows after the last group are no group's and are never written
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2 ** -7, atol=2 ** -7)


def _rule(family, phase):
    decode, prefill, scored, d, f = FAMILIES[family]
    rows = decode if phase == "decode" else prefill
    tm = held_row_tile(rows, scored)
    for k, n in ((d, f), (f, d)):
        tiling = gg.held_tiling(tm, k, n)
        if phase == "prefill":
            assert tm > TM
            assert tiling == (tm, min(k, 1024), min(n, 1024))
            continue
        assert tm == TM and tiling[2] == min(n, 1024)
        if 2 * k * min(n, 1024) * 2 <= gg.WEIGHT_TILES_BYTES:
            assert tiling[1] == k
        else:       # DeepSeek's and openPangu's hidden size: K tiles kept
            assert k > 7000 and tiling[1] == 1024


CASES = [(_product, (kind, widths, form)) for kind in SIZES
         for widths in WIDTHS for form in FORMS] + \
        [(_rule, (family, phase)) for family in FAMILIES
         for phase in ("decode", "prefill")]


@pytest.mark.parametrize("check,args", CASES,
                         ids=["-".join(a) for _, a in CASES])
def test_decode_tiles(check, args):
    """`grouped_gemm` over group sizes that straddle row tiles, are empty,
    leave rows after the last group or lie on one expert, at widths with and
    without remainders, under each form of the tiles; and `held_tiling` by
    shape: a family's decode call gets the whole K wherever two weight
    buffers of the widest N tile fit the budget, its prefill call the tiles
    it had."""
    check(*args)


def test_a_k_too_long_for_the_widest_n_tile_keeps_k_tiles():
    k = gg.WEIGHT_TILES_BYTES // (2 * 2 * 1024)
    assert gg.held_tiling(TM, k, 2048) == (TM, k, 1024)
    assert gg.held_tiling(TM, k + 128, 2048) == (TM, 1024, 1024)
    # a narrower result leaves room for a longer K; float32 weights for half
    assert gg.held_tiling(TM, 2 * k, 512) == (TM, 2 * k, 512)
    assert gg.held_tiling(TM, k, 2048, itemsize=4) == (TM, 1024, 1024)


def test_revisits_are_the_row_tiles_a_group_reaches_less_one():
    sizes = jnp.asarray([3, 3, 14, 0, 30], jnp.int32)
    assert int(jax.jit(gg.weight_tile_revisits, static_argnums=1)(
        sizes, TM)) == 0 + 0 + 1 + 0 + 2
    assert int(gg.weight_tile_revisits(jnp.zeros((5,), jnp.int32), TM)) == 0
    assert int(gg.weight_tile_revisits(jnp.asarray([16, 16, 32]), TM)) == 1
    # any other row tile keeps K tiles: no step finds its weights resident
    assert int(gg.weight_tile_revisits(sizes, 2 * TM)) == 0
    assert int(gg.weight_tile_revisits(sizes, None)) == 0
