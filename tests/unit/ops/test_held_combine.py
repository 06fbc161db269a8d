"""`ops/pallas/held_combine.py`: the way back of a held expert layer as one
pass over the expert-sorted rows, in the Pallas interpreter, against a
float64 sum over the rows in the order a stable sort lays them.

Tolerance: the kernel adds float32(row) x float32 weight in float32 with
every product exact (three bf16 limbs of a weight against a bf16 row) or at
the highest precision (float32 rows): 1e-6 of the largest output. A dropped
or doubled row moves the output by a tenth of it or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import held_combine as hc

TOL = 1e-6


def _sorted_sum(out_s, local, gate, count):
    """Each token's sum over its held rows, the rows found by the sort."""
    t, k = local.shape
    key = np.asarray(local).reshape(-1)
    order = np.argsort(key, kind="stable")
    rows = np.asarray(out_s.astype(jnp.float32)).astype(np.float64)
    weights = np.asarray(gate, np.float64).reshape(-1)
    out = np.zeros((t, rows.shape[1]))
    for r in range(int((key < count).sum())):
        out[order[r] // k] += rows[r] * weights[order[r]]
    return out


def _routing(kind, t, k, experts, count, key):
    gate, idx = jax.lax.top_k(jax.nn.sigmoid(
        jax.random.normal(key, (t, experts))), k)
    tok = jnp.arange(t)[:, None]
    if kind == "hot_tile":           # tokens 96-191 all choose held expert 0
        swapped = jnp.where(idx[:, 1:] == 0, idx[:, :1], idx[:, 1:])
        hot = jnp.concatenate([jnp.zeros_like(idx[:, :1]), swapped], axis=1)
        idx = jnp.where((tok >= 96) & (tok < 192), hot, idx)
    elif kind == "all_k_held":       # token 7's choices are held experts 0..k-1
        idx = jnp.where(tok == 7, jnp.arange(k)[None], idx)
    elif kind == "empty_tile":       # tokens 0-95 choose no held expert
        idx = jnp.where(tok < 96, count + idx % (experts - count), idx)
    local = jnp.where(idx < count, idx, count).astype(jnp.int32)
    if kind == "padding":            # `valid`: a padded token holds nothing
        local = jnp.where(tok % 5 == 3, count, local)
    return gate, local


# (id, routing, tokens, k, scored, held, hidden, rows over the held ones,
#  dtype, plan or None for the kernel's own)
CASES = [
    ("drawn_f32", "drawn", 288, 4, 16, 2, 32, 40, jnp.float32, None),
    ("drawn_bf16", "drawn", 288, 4, 16, 2, 256, 40, jnp.bfloat16, None),
    ("more_chunks_than_the_buffer", "hot_tile", 288, 4, 16, 2, 128, 9,
     jnp.bfloat16, (96, 32, 128)),
    ("all_k_choices_held", "all_k_held", 288, 4, 16, 4, 128, 16,
     jnp.bfloat16, None),
    ("a_tile_with_no_held_row", "empty_tile", 288, 4, 16, 2, 128, 16,
     jnp.bfloat16, (96, 32, 128)),
    ("padding_rows", "padding", 288, 4, 16, 2, 128, 16, jnp.bfloat16, None),
    ("held_at_the_bound", "drawn", 288, 4, 16, 2, 128, 0, jnp.bfloat16, None),
    ("hidden_of_three_blocks", "drawn", 288, 4, 16, 2, 384, 32, jnp.bfloat16,
     (96, 32, 128)),
    ("tokens_no_tile_divides", "drawn", 100, 4, 16, 2, 128, 16, jnp.bfloat16,
     None),
    ("eight_held_of_64", "drawn", 512, 8, 64, 8, 256, 100, jnp.bfloat16,
     None),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_held_combine_is_the_sorted_rows_sum(case, monkeypatch):
    """Every token gets the float32 sum of its held rows times their
    weights, whatever the windows: more chunks than the buffer holds
    (further passes),
    a token whose k choices are all held, a tile that holds nothing (zeros,
    no copy), padded tokens, held rows exactly at the bound, a hidden width
    of several blocks, a token count no tile divides. What lies at and past
    the held rows (NaN here) reaches no sum."""
    _, kind, t, k, experts, count, d, spare, dtype, plan = case
    keys = jax.random.split(jax.random.PRNGKey(61), 2)
    gate, local = _routing(kind, t, k, experts, count, keys[0])
    n = int((local < count).sum())
    out_s = jax.random.normal(keys[1], (n + spare, d), jnp.float32
                              ).astype(dtype).at[n:].set(jnp.nan)
    own = hc.combine_plan(t, n + spare, count, d, jnp.dtype(dtype).itemsize)
    if plan:
        monkeypatch.setattr(hc, "combine_plan", lambda *a: plan)
    tt, width, dblk = plan or own
    # the function under the module's own `jit`: a plan given here must not
    # meet another case's trace of the same shapes
    got = jax.jit(hc._held_combine, static_argnames="count")(
        out_s, local, gate, count)
    want = _sorted_sum(out_s, local, gate, count)
    assert got.shape == (t, d) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got, np.float64) - want).max() \
        < TOL * max(np.abs(want).max(), 1e-6)
    # the case is what its name says
    per_tile = np.asarray((local[:, :, None] == jnp.arange(count)).any(1)
                          )[:t // tt * tt].reshape(-1, tt, count).sum(1)
    if kind == "hot_tile":           # more chunks than the buffer: passes
        assert per_tile.max() > width
    if kind == "empty_tile":
        assert per_tile[0].sum() == 0 and not np.any(np.asarray(got[:tt]))
    if kind == "all_k_held":
        assert int((local[7] < count).sum()) == k
    if kind == "padding":
        assert not np.any(np.asarray(got)[np.arange(t) % 5 == 3])
    assert (d // dblk > 1) == (case[0] == "hidden_of_three_blocks")
    assert (t % tt != 0) == (case[0] == "tokens_no_tile_divides")


# the narrow body's calls in the benchmark's four cells and Ling's shape:
# tokens, rows the call is sized for, held experts, hidden
SHAPES = {"trinity": (16384, 32768, 16, 2048), "keye": (2048, 4096, 16, 2048),
          "deepseek": (2048, 2048, 16, 7168),
          "openpangu": (2048, 2048, 16, 7680),
          "ling": (8192, 32768, 128, 2560)}


@pytest.mark.parametrize("name", list(SHAPES))
def test_the_plan_comes_from_the_shapes_and_fits(name):
    """Whole tiles of tokens (128 to 512, fewer only where 128 held experts'
    chunks make the weight matrix wide), a buffer of whole 16-row chunks, a
    hidden block of whole lanes that divides the width, and the weight
    matrices, two slots of the buffer, two output tiles and the product's
    partials inside the 16 MiB a kernel may use without asking."""
    t, bound, count, d = SHAPES[name]
    tt, width, dblk = hc.combine_plan(t, bound, count, d, 2)
    assert t % tt == 0 and tt % 16 == 0
    assert (64 if name == "ling" else 128) <= tt <= 512
    assert d % dblk == 0 and dblk % 128 == 0
    assert 16 * tt * width + 2 * width * dblk * 2 + 5 * tt * dblk * 4 \
        <= 12 << 20
    # the buffer holds a tile's rows of a FULL bound and one more chunk an
    # expert for the draw-back; 512 rows in the four cells
    assert width == 16 * (-(-bound * tt // (16 * t)) + count)
    assert width == 512 or name == "ling"
