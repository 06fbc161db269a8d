"""A chunk round feeds every row it computes (paged layout, PR 36).

`put` gives each prompt that is prefilling one row of the round's width and
then the rows left over, a further chunk of `split_fuse_chunk` tokens each,
to the same prompts in admission order. Rows of one sequence at consecutive
cursors are, layer by layer, one longer chunk: every row's K/V is written
before the layer's attention reads the pool, and the attention masks by
absolute position. So nothing a prompt produces may depend on how many rows
of a round it rode in, to the bit.

The reference needs no switch in the source, and there are two of it. An
engine fed the same prompts `split_fuse_chunk` tokens a `put` (the prefill
continuation feed) runs one chunk a sequence a round, which is the schedule
from before; only a last piece of ONE token is no chunk there (`put` takes
it for a decode feed, and the decode rows' program rounds otherwise). So
those lengths, and prompts of more chunks than a round is wide, go through
the second: an engine whose round is CROWDED, sixteen prompts prefilling in
sixteen rows, so that no row is left over and each has one chunk a round,
whole prompts fed, chunk rows throughout.

CPU, float32, llama-tiny; both engines of a comparison run the same
programs at the same widths.
"""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.telemetry import TelemetryHub
from deepspeed_tpu.telemetry.hub import set_hub
from deepspeed_tpu.utils import groups

MAX_BATCH, CHUNK, MAX_SEQ = 6, 8, 96
K = 5
DECODER, JOINER = 100, 101
FILLER = 200                  # uids of the prompts that crowd a round


@pytest.fixture(scope="module")
def tiny():
    set_hub(TelemetryHub(enabled=False))
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    return (cfg,) + tuple(materialize_params(cfg))


def _engine(tiny, block=16, max_batch=MAX_BATCH, max_seq=MAX_SEQ, **kw):
    _, model, params = tiny
    groups.reset_topology()
    kw.setdefault("prefix_sharing", False)
    return InferenceEngineV2(model, params=params, max_batch=max_batch,
                             max_seq_len=max_seq, split_fuse_chunk=CHUNK,
                             cache_block_size=block, kv_layout="paged", **kw)


def _logical(eng, uid, upto=None):
    """A sequence's cache as the model reads it: K and V (and an int8
    pool's scales) of its first `upto` positions through its block table,
    every layer. Physical blocks differ between two schedules (they are
    taken in the order the rows reserve them); what they hold may not."""
    seq = eng.state_manager.get_sequence(uid)
    n = seq.seen_tokens if upto is None else upto
    bs = eng.state_manager.block_size
    blocks = np.asarray(eng._tables_np[seq.slot][:-(-n // bs)])
    assert (blocks >= 0).all()
    out = []
    for side in (eng.cache.k, eng.cache.v):
        pool = np.asarray(side.pool)[:, :, blocks]      # (L,Hkv,n_blk,BS,D)
        out.append(pool.reshape(pool.shape[:2] + (-1, pool.shape[-1]))[:, :, :n])
        if side.scales is not None:
            sc = np.asarray(side.scales)[:, :, blocks]
            out.append(sc.reshape(sc.shape[:2] + (-1,))[:, :, :n])
    return out


def _serve(eng, prompts, rounds, piece=None, crowd=0):
    """One decoding row from the start; then `prompts` (uid -> tokens) join
    together with one more short prompt, `piece` tokens of each a `put`
    (None: the whole prompt at once), and whatever has produced a token
    decodes along, fed its own argmax, for `rounds` rounds after the join.
    `crowd` long prompts join FIRST in that round: admitted first, they
    take what rows are left over, now and when a prompt finishes. Returns
    what each uid produced, in order, and the round in which each prompt
    produced its first."""
    vocab = eng.model_cfg.vocab_size
    rng = np.random.default_rng(5)
    short = [rng.integers(1, vocab, 5).astype(np.int32) for _ in range(2)]
    produced, feed, done_in = {}, {}, {}
    left = {uid: np.asarray(p, np.int32) for uid, p in prompts.items()}
    fillers = {FILLER + i: rng.integers(1, vocab, eng.max_seq_len - 8)
               for i in range(crowd)}

    def put(uids, toks, rnd):
        got = eng.put(list(feed) + uids, [[t] for t in feed.values()] + toks)
        for uid, o in got.items():
            if uid in left or uid in fillers:
                continue        # a piece is a whole feed to `put` and ends
                #                 in logits nobody asked for; a filler that
                #                 is through has done its work
            produced.setdefault(uid, []).append(np.asarray(o))
            feed[uid] = int(np.argmax(o))
            done_in.setdefault(uid, rnd)

    put([DECODER], [short[0]], 0)
    for rnd in range(1, rounds + 1):
        uids, toks = [], []
        if rnd == 1:
            uids = list(fillers) + [JOINER]
            toks = list(fillers.values()) + [short[1]]
        for uid in list(left):
            n = len(left[uid]) if piece is None else piece
            uids.append(uid)
            toks.append(left[uid][:n])
            left[uid] = left[uid][n:]
            if not len(left[uid]):
                del left[uid]
        put(uids, toks, rnd)
    assert not left
    return produced, done_in


def _both_ways(pair, prompts, **reference):
    """The prompts through the filled schedule (`pair[0]`, each fed whole,
    the rows of the round its own) and through one chunk a round
    (`pair[1]`, as `reference` says: `piece` or `crowd`): every logit row
    a prompt or a row beside it produced, and every sequence's cache, bit
    for bit as far as both went. Both engines are flushed."""
    filled, single = pair
    rounds = max(-(-len(p) // CHUNK) for p in prompts.values()) + 2
    try:
        new, new_done = _serve(filled, prompts, rounds)
        old, old_done = _serve(single, prompts, rounds, **reference)
        assert sorted(new) == sorted(old) == sorted([DECODER, JOINER,
                                                     *prompts])
        for uid in new:
            assert new[uid] and old[uid]
            for a, b in zip(new[uid], old[uid]):
                np.testing.assert_array_equal(a, b)
            upto = min(e.state_manager.get_sequence(uid).seen_tokens
                       for e in pair)
            assert upto >= len(prompts.get(uid, ()))
            for a, b in zip(_logical(filled, uid, upto),
                            _logical(single, uid, upto)):
                np.testing.assert_array_equal(a, b)
        for eng in pair:        # the device's cursors are the host's
            index = np.asarray(eng.cache.index)
            for uid in new:
                seq = eng.state_manager.get_sequence(uid)
                assert index[seq.slot] == seq.seen_tokens
        for uid, p in prompts.items():
            assert old_done[uid] == -(-len(p) // CHUNK)
            assert new_done[uid] <= old_done[uid]
    finally:
        for eng in pair:
            eng._flush_batch(list(eng.state_manager.tracked_sequences))
    return new_done, old_done


# --------------------------------- against an engine fed a chunk a `put`


# blocks of 16 hold two rows of one round, blocks of 12 are straddled by a
# row (8..16 crosses 12), blocks of 4 are spanned two a row
@pytest.fixture(scope="module", params=[16, 12, 4],
                ids=lambda b: f"block{b}")
def pair(tiny, request):
    return [_engine(tiny, block=request.param) for _ in range(2)]


# one token, a chunk less one, a chunk, K chunks, K chunks and 5
@pytest.mark.parametrize("length", [1, CHUNK - 1, CHUNK, CHUNK * K,
                                    CHUNK * K + 5])
def test_a_prompt_alone_is_bit_for_bit_the_one_chunk_a_put_feed(tiny, pair,
                                                                length):
    rng = np.random.default_rng(length)
    prompt = rng.integers(1, tiny[0].vocab_size, length)
    c0 = pair[0].serving_counters["rows_refilled"]
    new_done, _ = _both_ways(pair, {1: prompt}, piece=CHUNK)
    chunks = -(-length // CHUNK)
    # the short prompt that joins with it has the round's first row, the
    # prompt the five others, and all six of the next round
    assert new_done[1] == (1 if chunks <= MAX_BATCH - 1 else 2)
    assert pair[0].serving_counters["rows_refilled"] - c0 == chunks - \
        new_done[1]
    assert pair[1].serving_counters["rows_refilled"] == 0


@pytest.mark.parametrize("lengths", [
    (CHUNK * K + 5, CHUNK * K, CHUNK - 1), (1, CHUNK * K + 5, CHUNK),
    (CHUNK, CHUNK * 2, CHUNK * K), (CHUNK * 3 + 2, CHUNK * 3 + 4, CHUNK * 3)],
    ids=lambda ls: "-".join(map(str, ls)))
def test_three_prompts_at_once_are_bit_for_bit_the_one_chunk_a_put_feed(
        tiny, pair, lengths):
    """Four prompts for six rows in the joining round (the first two have
    two rows), then the rows that finished prompts leave go to those still
    prefilling."""
    rng = np.random.default_rng(sum(lengths))
    prompts = {uid: rng.integers(1, tiny[0].vocab_size, n)
               for uid, n in enumerate(lengths, 1)}
    new_done, old_done = _both_ways(pair, prompts, piece=CHUNK)
    assert max(new_done.values()) < max(old_done.values())


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_bit_for_bit_with_the_kernels_on(tiny, monkeypatch, kv):
    """The chip's path, interpreted: `kv_write_paged` read-modify-writes one
    block from two rows of a sequence one after the other, and
    `self_attn_paged_prefill` masks each row by its own absolute
    positions."""
    import deepspeed_tpu.ops.attention as attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    kw = {"kv_cache_dtype": "int8"} if kv == "int8" else {}
    pair = [_engine(tiny, block=16, **kw) for _ in range(2)]
    rng = np.random.default_rng(36)
    prompts = {1: rng.integers(1, tiny[0].vocab_size, CHUNK * 3 + 5),
               2: rng.integers(1, tiny[0].vocab_size, CHUNK + 2)}
    new_done, old_done = _both_ways(pair, prompts, piece=CHUNK)
    # six rows for three prompts: the first of them (after the joiner, one
    # row) has chunks for all three left over
    assert (new_done, old_done) == ({DECODER: 0, JOINER: 1, 1: 1, 2: 2},
                                    {DECODER: 0, JOINER: 1, 1: 4, 2: 2})
    assert pair[0].serving_counters["rows_refilled"] == 3


# ----------------------------------- against an engine whose round is crowded

WIDE, NARROW, LONG_SEQ = 18, 16, 208      # rungs (16, 18); a prompt of 25 chunks


@pytest.fixture(scope="module", params=[16, 12], ids=lambda b: f"block{b}")
def wide_pair(tiny, request):
    return [_engine(tiny, block=request.param, max_batch=WIDE,
                    max_seq=LONG_SEQ) for _ in range(2)]


# the six lengths of the issue at a chunk of 8, and two of more chunks than
# a narrow round has rows: twenty, and twenty and one token
@pytest.mark.parametrize("length", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                                    CHUNK * K, CHUNK * K + 5, CHUNK * 20,
                                    CHUNK * 20 + 1])
def test_a_prompt_alone_is_bit_for_bit_its_one_row_of_a_crowded_round(
        tiny, wide_pair, length):
    rng = np.random.default_rng(length)
    prompt = rng.integers(1, tiny[0].vocab_size, length)
    new_done, _ = _both_ways(wide_pair, {1: prompt}, crowd=NARROW - 2)
    assert new_done[1] == (1 if length <= CHUNK * (NARROW - 1) else 2)


@pytest.mark.parametrize("lengths", [
    (CHUNK * K + 5, CHUNK * K, CHUNK + 1), (1, CHUNK * 2 + 1, CHUNK - 1),
    (CHUNK * 9, CHUNK * 9 + 1, CHUNK * 3)],
    ids=lambda ls: "-".join(map(str, ls)))
def test_three_prompts_at_once_are_bit_for_bit_their_rows_of_a_crowded_round(
        tiny, wide_pair, lengths):
    rng = np.random.default_rng(sum(lengths))
    prompts = {uid: rng.integers(1, tiny[0].vocab_size, n)
               for uid, n in enumerate(lengths, 1)}
    new_done, old_done = _both_ways(wide_pair, prompts, crowd=NARROW - 4)
    assert max(new_done.values()) <= 2 < max(old_done.values())


# ------------------------------------------------------------- the program


def _rows(eng, order, slot, start, valids):
    """Operands of `chunk_batch`: the i-th row of the sequence in `slot`
    (`valids[i]` tokens from where the row before it ended) stands at
    position `order[i]` of the width, every other row parked."""
    ids, slots, starts, vals = eng._parked_rows(eng.max_batch)
    rng = np.random.default_rng(3)
    toks = rng.integers(1, eng.model_cfg.vocab_size, (len(valids), CHUNK))
    at = start
    for i, (pos, valid) in enumerate(zip(order, valids)):
        ids[pos, :valid] = toks[i, :valid]
        slots[pos] = slot if i == len(valids) - 1 else ~slot
        starts[pos], vals[pos] = at, valid
        at += valid
    return tuple(map(jnp.asarray, (ids, slots, starts, vals)))


@pytest.fixture(scope="module")
def bare(tiny):
    """An engine whose slot 2 owns blocks for 5 + 19 tokens, and a copy of
    its cache to start every case from (the programs donate theirs)."""
    eng = _engine(tiny, block=12)
    seqs = [eng.state_manager.get_or_create_sequence(7 + i) for i in range(3)]
    (seq,) = [q for q in seqs if q.slot == 2]
    eng._reserve(seq, 5 + 2 * CHUNK + 3)
    eng._maybe_sync_tables()
    return eng, seq, jax.tree_util.tree_map(np.asarray, eng.cache)


@pytest.mark.parametrize("order", list(itertools.permutations(range(0, 6, 2),
                                                              3))
                         + [(5, 4, 3), (1, 5, 0), (4, 0, 1)],
                         ids=lambda o: "".join(map(str, o)))
def test_cursor_is_the_last_rows_wherever_the_rows_stand(bare, order):
    """Under `jit` (the registered program), three rows of slot 2 at any
    positions of a width of six: the slot's cursor ends at `start` plus
    all their tokens, no other cursor moves, and the sequence's last row
    gives the logits, and the rows the cache, that they give in order."""
    eng, seq, snapshot = bare
    slot, start, valids = seq.slot, 5, (CHUNK, CHUNK, 3)
    seq.seen_tokens = start + sum(valids)
    results = []
    for o in (order, (0, 1, 2)):
        eng.cache = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, snapshot), eng._cache_pin)
        eng.cache, last = eng._chunk_batch_fn()(
            eng.params, eng.cache, *_rows(eng, o, slot, start, valids))
        results.append((np.asarray(eng.cache.index), np.asarray(last[o[-1]]),
                        _logical(eng, seq.uid)))
    (index, last, logical), in_order = results
    want = np.asarray(snapshot.index).copy()
    assert (want == eng.cache.max_len).all()       # every row was parked
    want[slot] = start + sum(valids)
    np.testing.assert_array_equal(index, want)
    np.testing.assert_array_equal(last, in_order[1])
    for a, b in zip(logical, in_order[2]):
        np.testing.assert_array_equal(a[:, :, start:], b[:, :, start:])


# ----------------------------------------------------------- the scheduler


@pytest.fixture(scope="module")
def by_rows(tiny):
    """An engine for each `max_batch` asked for, kept for the module; a
    test leaves it with nothing tracked."""
    engines = {}

    def get(max_batch):
        if max_batch not in engines:
            engines[max_batch] = _engine(tiny, max_batch=max_batch,
                                         max_seq=40 * CHUNK + 8)
        return engines[max_batch]
    return get


def _chunk_rounds(eng, uids, prompts):
    """Rounds until no prompt is pending: the tokens each sequence advanced
    by in each round, and the round each produced its logits in. Flushed."""
    advanced, done = [], {}
    seqs, before = None, [0] * len(uids)
    while seqs is None or any(q.pending for q in seqs):
        got = eng.put([] if seqs else uids, [] if seqs else prompts)
        seqs = seqs or [eng.state_manager.get_sequence(u) for u in uids]
        after = [q.seen_tokens for q in seqs]
        advanced.append([a - b for a, b in zip(after, before)])
        before = after
        for uid in got:
            done[uid] = len(advanced)
    eng._flush_batch(uids)
    return advanced, done


@pytest.mark.parametrize("max_batch,k", [(4, 1), (4, 4), (4, 5), (4, 9),
                                         (20, 16), (20, 17), (20, 40)])
def test_a_lone_prompt_takes_its_chunks_over_the_width_in_rounds(
        tiny, by_rows, max_batch, k):
    """`k` whole chunks alone: R rows a round (R the narrow width where
    `max_batch` has one), so `ceil(k / R)` rounds, each but the last full."""
    eng = by_rows(max_batch)
    width = min(16, max_batch)
    c0 = dict(eng.serving_counters)
    prompt = np.random.default_rng(k).integers(1, tiny[0].vocab_size,
                                               k * CHUNK)
    advanced, done = _chunk_rounds(eng, [1], [prompt])
    c1 = eng.serving_counters
    if k == 1:     # no longer than a chunk: the single-shot prefill
        assert advanced == [[CHUNK]] and done == {1: 1}
        assert c1["token_slots_computed"] - c0["token_slots_computed"] == 32
        return
    assert len(advanced) == done[1] == -(-k // width)
    assert [a for (a,) in advanced[:-1]] == [width * CHUNK] * (done[1] - 1)
    assert c1["rows_refilled"] - c0["rows_refilled"] == k - done[1]
    assert c1["tokens_fed"] - c0["tokens_fed"] == k * CHUNK


@pytest.mark.parametrize("max_batch,lengths", [
    (4, (40, 40, 40)),             # three prompts, one row left over
    (4, (40, 9, 40, 40)),          # four for four rows: none left over
    (4, (9, 40, 40)),              # the first cannot use the spare row
    (20, tuple(range(17, 17 + 14))),    # fourteen for sixteen rows
    (20, tuple(range(17, 17 + 18)))],   # eighteen: the wide round, two over
    ids=["3for4", "4for4", "short_first", "14for16", "18for20"])
def test_every_prompt_pending_has_a_row_and_the_first_take_the_rest(
        tiny, by_rows, max_batch, lengths):
    eng = by_rows(max_batch)
    rng = np.random.default_rng(len(lengths))
    uids = list(range(1, len(lengths) + 1))
    prompts = [rng.integers(1, tiny[0].vocab_size, n) for n in lengths]
    advanced, done = _chunk_rounds(eng, uids, prompts)
    left = list(lengths)
    for adv in advanced:
        waiting = sum(n > 0 for n in left)
        width = 16 if max_batch > 16 and waiting <= 16 else max_batch
        spare = width - waiting
        for i, a in enumerate(adv):
            if not left[i]:
                assert a == 0
                continue
            # at least one row, whoever else waits; then, in admission
            # order, as many more as are left over and it has chunks for
            assert a >= min(CHUNK, left[i])
            more = min(spare, -(-left[i] // CHUNK) - 1)
            assert a == min(left[i], (1 + more) * CHUNK)
            spare -= more
            left[i] -= a
    assert not any(left) and sorted(done) == uids


# ------------------------------------------- prefix sharing, fork, int8 pool


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_shared_prefix_fork_and_copy_on_write_through_a_filled_round(tiny,
                                                                     kv):
    """A second prompt starts at its matched cursor (two shared blocks) and
    runs the rest of its rows in one round; a fork's child is then fed a
    continuation of three rows, the first of which lands in the partial
    tail block it shares with its parent (copy-on-write in `_reserve`,
    drained by the round's `sync`). Against the same feeds in a crowded
    round, a chunk a round: logits, caches, and the parent untouched."""
    kw = dict(block=8, prefix_sharing=True, max_batch=WIDE, max_seq=LONG_SEQ,
              **({"kv_cache_dtype": "int8"} if kv == "int8" else {}))
    vocab = tiny[0].vocab_size
    rng = np.random.default_rng(8)
    shared = rng.integers(1, vocab, 16)                   # two whole blocks
    first = np.concatenate([shared, rng.integers(1, vocab, 13)])   # 29
    second = np.concatenate([shared, rng.integers(1, vocab, 21)])  # 37
    more = rng.integers(1, vocab, 19)          # the child's continuation
    runs = []
    for crowd in (0, NARROW - 1):
        eng = _engine(tiny, **kw)
        rounds = [0]
        if crowd:      # fifteen prompts and each sequence below: sixteen
            eng.put([FILLER + i for i in range(crowd)],
                    list(rng.integers(1, vocab, (crowd, LONG_SEQ - 8))))

        def feed(uid, toks):
            """`toks` to `uid`; its last logits and the rounds it took."""
            got, n = eng.put([uid], [toks]), 1
            while uid not in got:
                got, n = eng.put([], []), n + 1
            rounds.append(n)
            return np.asarray(got[uid])

        outs = [feed(1, first), feed(2, second)]
        assert eng.block_manager.prefix_hits == 1
        assert eng.block_manager.prefix_tokens_reused == len(shared)
        eng.fork(1, 3)
        parent_before = _logical(eng, 1)
        outs.append(feed(3, more))
        for a, b in zip(parent_before, _logical(eng, 1)):
            np.testing.assert_array_equal(a, b)
        # parent and child part ways at the tail block, and only there
        t1, t3 = (eng._tables_np[eng.state_manager.get_sequence(u).slot]
                  for u in (1, 3))
        tail = len(first) // 8
        assert t1[tail] != t3[tail] and (t1[:tail] == t3[:tail]).all()
        got = eng.put([1, 2, 3], [[4], [5], [6]])
        outs += [np.asarray(got[u]) for u in (1, 2, 3)]
        runs.append((outs, [_logical(eng, u) for u in (1, 2, 3)], rounds[1:],
                     eng.serving_counters["rows_refilled"]))
    (new, new_cache, new_rounds, refilled), (old, old_cache, old_rounds, _) = \
        runs
    for a, b in zip(new, old):
        np.testing.assert_array_equal(a, b)
    for seq_a, seq_b in zip(new_cache, old_cache):
        for a, b in zip(seq_a, seq_b):
            np.testing.assert_array_equal(a, b)
    # 29 tokens are 4 rows, the 21 past the match 3, the 19 more 3
    assert (new_rounds, old_rounds) == ([1, 1, 1], [4, 3, 3])
    assert refilled == 3 + 2 + 2
