"""Chunk rounds as wide as the prompts that are prefilling (paged layout).

The chunk half of `fused_batch` comes in a short ladder of row widths and
`put` takes the narrowest that holds the round's pending prompts; a narrow
round with nothing to decode rides it too, `chunk_batch` being `max_batch`
wide only. What must hold: a row's outputs do not depend on the width it
rode in, every program is compiled before the first chunk round returns,
the counters count the width that ran, and the ladder always ends at
`max_batch`.

CPU, float32, llama-tiny; 20 rows, so the rungs are 16 and 20.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import chunk_row_widths, width_for
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry import (TelemetryHub, compile_totals,
                                     get_span_store)
from deepspeed_tpu.telemetry.hub import set_hub
from deepspeed_tpu.utils import groups

MAX_BATCH, CHUNK = 20, 8
WIDTHS = (16, 20)
SHORT = 5                     # a lone prompt this long takes the prefill bucket


@pytest.fixture(scope="module")
def tiny():
    set_hub(TelemetryHub(enabled=False))
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    return (cfg,) + tuple(materialize_params(cfg))


def _engine(tiny):
    _, model, params = tiny
    groups.reset_topology()
    return InferenceEngineV2(model, params=params, max_batch=MAX_BATCH,
                             max_seq_len=64, split_fuse_chunk=CHUNK,
                             cache_block_size=16, kv_layout="paged",
                             prefix_sharing=False)


def _prompts(vocab, n, seed):
    """`n` prompts longer than a chunk, of lengths that differ (so rows end
    in different rounds and the round narrows) and that are no multiple of
    the chunk (so each ends in a short chunk)."""
    rng = np.random.default_rng([seed, n])
    return [rng.integers(1, vocab, CHUNK + 1 + (3 * i) % 17).astype(np.int32)
            for i in range(n)]


def _serve(eng, vocab, pending, decoding, seed, argmax_only=False):
    """One scripted mix: `decoding` short prompts join one by one and then
    decode along, `pending` long prompts join together, rounds run until
    every prompt is in, then two rounds of plain decode; everything is
    flushed. Returns what each round gave, by uid."""
    rounds, feed = [], {}
    rng = np.random.default_rng([seed, 7])

    def put(uids, toks):
        uids, toks = list(feed) + uids, [[t] for t in feed.values()] + toks
        got = eng.put(uids, toks, argmax_only=argmax_only)
        for uid, o in got.items():
            feed[uid] = int(o) if argmax_only else int(np.argmax(o))
        rounds.append({u: np.asarray(o) for u, o in got.items()})

    for d in range(decoding):
        put([100 + d], [rng.integers(1, vocab, SHORT).astype(np.int32)])
    put(list(range(1, pending + 1)), _prompts(vocab, pending, seed))
    while any(s.pending for s in eng.state_manager.tracked_sequences.values()):
        put([], [])
    put([], [])
    put([], [])
    eng._flush_batch(list(feed))
    return rounds


def _by_uid(rounds):
    """What each uid produced, in order, whatever the round it came in."""
    out = {}
    for got in rounds:
        for uid, o in got.items():
            out.setdefault(uid, []).append(o)
    return out


def _filled(lengths, width):
    """(rows that carry tokens, tokens fed) of the first chunk round of
    prompts of these lengths in a round `width` rows wide: one row each in
    admission order, then the rows left over to the same prompts in the
    same order, a chunk a row."""
    takes, spare = [], width - len(lengths)
    for n in lengths:
        more = min(spare, -(-n // CHUNK) - 1)
        takes.append(1 + more)
        spare -= more
    return sum(takes), sum(min(t * CHUNK, n) for t, n in zip(takes, lengths))


# (rows pending, rows decoding): every rung, with and without decode rows
MIXES = [(1, 0), (1, 2), (3, 0), (3, 2), (7, 0), (7, 2), (13, 0), (13, 3),
         (MAX_BATCH, 0), (MAX_BATCH - 1, 1)]


@pytest.fixture(scope="module")
def transcripts(tiny):
    """The same scripts on the ladder and on the ladder collapsed to
    `[max_batch]` (what every chunk round was before there was a ladder)."""
    vocab = tiny[0].vocab_size
    out = {}
    mp = pytest.MonkeyPatch()
    for name in ("ladder", "collapsed"):
        if name == "collapsed":
            mp.setattr(engine_v2, "chunk_row_widths", lambda mb: (mb,))
        try:
            eng = _engine(tiny)
            out[name] = [_serve(eng, vocab, p, d, seed=i)
                         for i, (p, d) in enumerate(MIXES)]
            out[name + "_programs"] = sorted(eng.recompiles._seen)
        finally:
            mp.undo()
    return out


@pytest.mark.parametrize("case", range(len(MIXES)),
                         ids=[f"pending{p}-decoding{d}" for p, d in MIXES])
def test_a_rows_outputs_do_not_depend_on_the_width_it_rode_in(transcripts,
                                                              case):
    ladder, flat = (_by_uid(transcripts[name][case])
                    for name in ("ladder", "collapsed"))
    # a wider round has more rows to fill, so a prompt may be in a round
    # sooner and the rows beside it decode less often until the script
    # ends: what a uid produced is compared output for output, as far as
    # both runs went
    assert sorted(ladder) == sorted(flat)
    for uid in ladder:
        for a, b in zip(ladder[uid], flat[uid]):
            assert int(np.argmax(a)) == int(np.argmax(b))
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    pending, decoding = MIXES[case]
    assert len(ladder) == pending + decoding
    assert all(ladder[uid] and flat[uid] for uid in ladder)


def _family(widths):
    return sorted([f"chunk_batch:{CHUNK}"] +
                  [f"fused_batch:{CHUNK}:{w}" for w in widths])


def test_the_two_runs_differ_only_in_the_ladder(transcripts):
    assert [p for p in transcripts["ladder_programs"] if "batch" in p] == \
        _family(WIDTHS)
    assert [p for p in transcripts["collapsed_programs"] if "batch" in p] == \
        _family([MAX_BATCH])


@pytest.fixture(scope="module")
def warmed(tiny):
    """An engine that has served one lone prompt and one chunked prompt (the
    first chunk round, which compiles the family) with decode rows beside
    it, and flushed them."""
    eng = _engine(tiny)
    _serve(eng, tiny[0].vocab_size, 1, 1, seed=99, argmax_only=True)
    return eng


@pytest.mark.parametrize("decoding", [0, 1], ids=["alone", "fused"])
@pytest.mark.parametrize("pending", range(1, MAX_BATCH + 1))
def test_no_pending_count_compiles_after_the_first_chunk_round(
        tiny, warmed, pending, decoding):
    if pending + decoding > MAX_BATCH:
        pytest.skip("every row is prefilling: none is left to decode")
    n0, m0 = compile_totals()[0], warmed.recompiles.pinned_misses
    programs = set(warmed.recompiles._seen)
    _serve(warmed, tiny[0].vocab_size, pending, decoding, seed=pending,
           argmax_only=True)
    assert compile_totals()[0] == n0
    assert warmed.recompiles.pinned_misses == m0
    assert set(warmed.recompiles._seen) == programs


def test_the_first_chunk_round_compiles_every_width_and_counts_one_round(tiny):
    eng = _engine(tiny)
    (prompt,) = _prompts(tiny[0].vocab_size, 1, 0)
    got = eng.put([1], [prompt], argmax_only=True)
    assert sorted(eng.recompiles._seen) == _family(WIDTHS)
    c = eng.serving_counters
    # the parked dispatches are not rounds and feed nothing; the one round
    # rode the narrowest `fused_batch`, whose decode half ran idle, and
    # held the whole prompt (two rows of it)
    assert (c["rounds"], c["token_slots_computed"], c["tokens_fed"]) == \
        (1, WIDTHS[0] * CHUNK + MAX_BATCH, len(prompt))
    assert list(got) == [1] and c["rows_refilled"] == 1


@pytest.mark.parametrize("decoding", [0, 1], ids=["alone", "fused"])
@pytest.mark.parametrize("pending,width", [(1, 16), (8, 16), (9, 16),
                                           (16, 16), (17, 20), (19, 20)])
def test_counters_and_span_carry_the_width_that_ran(tiny, warmed, pending,
                                                    width, decoding):
    store = get_span_store()
    vocab = tiny[0].vocab_size
    rng = np.random.default_rng(pending)
    feed = {}
    for d in range(decoding):
        got = warmed.put([100 + d], [rng.integers(1, vocab, SHORT)],
                         argmax_only=True)
        feed[100 + d] = int(got[100 + d])
    warmed.tracer.force = True
    store.clear()
    try:
        c0 = dict(warmed.serving_counters)
        uids = list(range(1, pending + 1))
        prompts = _prompts(vocab, pending, 0)
        warmed.put(list(feed) + uids, [[t] for t in feed.values()] + prompts,
                   argmax_only=True)
        c1 = warmed.serving_counters
        fused = bool(decoding) or width < MAX_BATCH
        slots = width * CHUNK + (MAX_BATCH if fused else 0)
        filled, fed = _filled([len(p) for p in prompts], width)
        assert pending <= filled <= width
        assert c1["token_slots_computed"] - c0["token_slots_computed"] == slots
        assert c1["tokens_fed"] - c0["tokens_fed"] == fed + decoding
        assert c1["rows_refilled"] - c0["rows_refilled"] == filled - pending
        (chunk,) = [s for s in store.spans() if s["name"] == "chunk"]
        f = chunk["fields"]
        assert (f["rows"], f["sequences"], f["width"], f["fused"]) == (
            filled, pending, width, fused)
        assert (f["token_slots"], f["tokens_fed"]) == (slots, fed + decoding)
        # the rows the paged kernels skip: the rows of the width that no
        # prompt could fill, and in the decode half (which runs first)
        # every row but those that decode, the prompts that join in this
        # round included
        rows = width + (MAX_BATCH if fused else 0)
        live = filled + (decoding if fused else 0)
        assert (f["rows_live"], f["rows_parked"]) == (live, rows - live)
        assert c1["rows_parked"] - c0["rows_parked"] == rows - live
        (disp,) = [s for s in store.spans() if s["name"] == "dispatch"]
        assert disp["fields"]["program"] == (
            f"fused_batch:{CHUNK}:{width}" if fused else f"chunk_batch:{CHUNK}")
        assert disp["fields"]["compiled"] is False
    finally:
        warmed.tracer.force = False
        store.clear()
        warmed._flush_batch(list(feed) + uids)


@pytest.fixture(scope="module")
def either_path(tiny):
    """`serve(kernels, pending, decoding)`: the mix served by the module's
    engine on the XLA path or on the chip's path interpreted (each built and
    traced with `_use_pallas` saying so, and kept: an engine compiles every
    program in its first chunk round, the kernels-on one for a quarter of a
    minute); the rounds, the engine, and what its counters counted over
    them. `_serve` leaves an engine with nothing tracked."""
    import deepspeed_tpu.ops.attention as attention
    engines = {}

    def serve(kernels, pending, decoding):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(attention, "_use_pallas", lambda: kernels)
            if kernels not in engines:
                engines[kernels] = _engine(tiny)
            eng = engines[kernels]
            before = dict(eng.serving_counters)
            rounds = _serve(eng, tiny[0].vocab_size, pending, decoding,
                            seed=31)
        return rounds, eng, {k: v - before[k]
                             for k, v in eng.serving_counters.items()}
    return serve


@pytest.mark.parametrize("pending,decoding", [(2, 1), (17, 1)],
                         ids=["narrow", "wide"])
def test_rounds_of_mostly_parked_rows_give_the_same_tokens_with_the_kernels_on(
        either_path, pending, decoding):
    """Served rounds of `fused_batch` at each width, most rows parked in
    the round the prompts join in and the live ones several to a prompt,
    through the chip's path interpreted (both paged kernels, which skip
    the parked rows, and the Pallas writer, which writes one block from
    two rows) against the XLA path, which holds no kernel: the same
    tokens, round for round."""
    runs = []
    for kernels in (False, True):
        rounds, eng, counted = either_path(kernels, pending, decoding)
        runs.append(rounds)
        width = f"fused_batch:{CHUNK}:{width_for(pending, MAX_BATCH)}"
        assert width in eng.recompiles._seen
        # the joining round alone parks more rows than a program is wide,
        # and in it every prompt has two rows or three: live rows of one
        # round that share a slot, a block and a cursor's scatter
        assert counted["rows_parked"] > MAX_BATCH
        assert counted["rows_refilled"] >= min(pending, 3)
    xla, pallas = runs
    assert len(xla) == len(pallas)
    for a, b in zip(xla, pallas):
        assert sorted(a) == sorted(b)
        for uid in a:
            assert int(np.argmax(a[uid])) == int(np.argmax(b[uid]))
            np.testing.assert_allclose(a[uid], b[uid], rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("max_batch", [1, 2, 3, 4, 5, 8, 9, 16, 17, 20, 48,
                                       64, 256])
def test_width_for_holds_the_rows_and_the_ladder_ends_at_max_batch(max_batch):
    ladder = chunk_row_widths(max_batch)
    assert 1 <= len(ladder) <= 2 and ladder[-1] == max_batch
    assert list(ladder) == sorted(set(ladder))
    for rows in range(1, max_batch + 1):
        w = width_for(rows, max_batch)
        assert w >= rows and w in ladder
        assert not [v for v in ladder if rows <= v < w]   # the narrowest
    assert chunk_row_widths(48) == (16, 48)
