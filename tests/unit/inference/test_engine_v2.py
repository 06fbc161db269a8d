"""Inference v2 (FastGen analog) tests — reference tests/unit/inference/v2:
allocator behavior, ragged state, continuous-batching parity with the v1
engine."""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import (
    BlockedAllocator, DSStateManager, InferenceEngineV2)
from deepspeed_tpu.models.llama import llama_config, materialize_params
from deepspeed_tpu.utils import groups


def test_blocked_allocator():
    a = BlockedAllocator(4)
    got = a.allocate(3)
    assert len(got) == 3 and a.free_blocks == 1
    with pytest.raises(RuntimeError):
        a.allocate(2)
    a.free(got[0])
    assert a.free_blocks == 2
    with pytest.raises(ValueError):
        a.free(got[0])


def test_state_manager_slots():
    sm = DSStateManager(2)
    s1 = sm.get_or_create_sequence(10)
    s2 = sm.get_or_create_sequence(11)
    assert {s1.slot, s2.slot} == {0, 1}
    with pytest.raises(RuntimeError):
        sm.get_or_create_sequence(12)
    sm.flush_sequence(10)
    s3 = sm.get_or_create_sequence(12)
    assert s3.slot == s1.slot  # slot reuse


@pytest.fixture
def tiny():
    cfg = llama_config("llama-tiny", dtype=jnp.float32)
    model, params = materialize_params(cfg)
    return cfg, model, params


@pytest.mark.slow
def test_v2_matches_v1_greedy(tiny):
    """Continuous batching must not change greedy outputs: each sequence's
    result equals the v1 engine run alone."""
    cfg, model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9, 7, 12, 6)]

    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    # max_batch=2 < 5 prompts → forced continuous batching (join/leave)
    outs = v2.generate(prompts, max_new_tokens=6)

    groups.reset_topology()
    v1 = deepspeed_tpu.init_inference(model, params=params, dtype="fp32")
    for prompt, got in zip(prompts, outs):
        ref = v1.generate(np.asarray([prompt]), max_new_tokens=6)[0]
        np.testing.assert_array_equal(np.asarray(got), ref)


def test_v2_put_flush_cycle(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=32)
    logits = v2.put([1], [np.asarray([3, 5, 7], np.int32)])
    assert logits[1].shape == (cfg.vocab_size,)
    assert v2.state_manager.n_tracked_sequences == 1
    # continuation via batched decode
    out = v2.put([1], [np.asarray([int(np.argmax(logits[1]))], np.int32)])
    assert out[1].shape == (cfg.vocab_size,)
    assert v2.state_manager.get_sequence(1).seen_tokens == 4
    v2.flush(1)
    assert v2.state_manager.n_tracked_sequences == 0
    assert v2.can_schedule([2, 3], [8, 8])
    assert not v2.can_schedule([2, 3, 4], [8, 8, 8])


def test_v2_interleaved_decode_isolated(tiny):
    """A sequence's decode must be unaffected by neighbors joining and
    leaving other slots (cache-slot isolation)."""
    cfg, model, params = tiny
    rng = np.random.default_rng(1)
    p_main = list(rng.integers(0, cfg.vocab_size, 6))
    p_other = [list(rng.integers(0, cfg.vocab_size, 4)) for _ in range(3)]

    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    # run main alone first
    ref = v2.generate([p_main], max_new_tokens=8)[0]

    groups.reset_topology()
    v2b = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    # main + churning neighbors
    logits = v2b.put([0], [np.asarray(p_main, np.int32)])[0]
    seq = [*p_main, int(np.argmax(logits))]
    neighbor = iter(p_other)
    v2b.put([100], [np.asarray(next(neighbor), np.int32)])
    for step in range(7):
        out = v2b.put([0], [[seq[-1]]])
        seq.append(int(np.argmax(out[0])))
        if step == 2:
            v2b.flush(100)
            v2b.put([101], [np.asarray(next(neighbor), np.int32)])
        if step == 4:
            v2b.put([101], [[7]])
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(ref))


def test_split_fuse_long_prompt_parity(tiny):
    """Chunked prefill (split-fuse) must be bit-identical to single-shot
    prefill: same cache contents, same greedy continuation."""
    cfg, model, params = tiny
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(0, cfg.vocab_size, 41))

    groups.reset_topology()
    ref_eng = InferenceEngineV2(model, params=params, max_batch=2,
                                max_seq_len=64, split_fuse_chunk=1024)
    ref = ref_eng.generate([prompt], max_new_tokens=6)[0]

    groups.reset_topology()
    sf = InferenceEngineV2(model, params=params, max_batch=2,
                           max_seq_len=64, split_fuse_chunk=16)
    got = sf.generate([prompt], max_new_tokens=6)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.slow
def test_split_fuse_decode_rides_chunk_step(tiny):
    """A live sequence keeps decoding in the SAME put that chunks a long
    prompt (the fused program), and its tokens match a run without the
    intruding prompt."""
    cfg, model, params = tiny
    rng = np.random.default_rng(3)
    p_a = list(rng.integers(0, cfg.vocab_size, 5))
    p_b = list(rng.integers(0, cfg.vocab_size, 30))

    groups.reset_topology()
    solo = InferenceEngineV2(model, params=params, max_batch=2,
                             max_seq_len=64, split_fuse_chunk=8)
    ref_a = solo.generate([p_a], max_new_tokens=6)[0]

    groups.reset_topology()
    both = InferenceEngineV2(model, params=params, max_batch=2,
                             max_seq_len=64, split_fuse_chunk=8)
    la = both.put([0], [np.asarray(p_a, np.int32)])[0]
    seq_a = [*p_a, int(np.argmax(la))]
    # B's long prompt arrives while A decodes: each put advances A by one
    # token AND B by a round's chunk rows in the SAME fused step; B (30
    # tokens, chunk 8 → 4 chunks, two rows a round at max_batch 2)
    # completes on the 2nd round without ever stalling A.
    b_logits = None
    done_in = None
    for rounds in range(1, 5):
        outs = both.put([0], [[seq_a[-1]]]) if rounds > 1 else \
            both.put([0, 1], [[seq_a[-1]], np.asarray(p_b, np.int32)])
        assert 0 in outs          # A decoded every round
        seq_a.append(int(np.argmax(outs[0])))
        if 1 in outs:
            b_logits, done_in = outs[1], rounds
    assert b_logits is not None and done_in == 2  # B done on the last chunk
    seq_a.append(int(np.argmax(both.put([0], [[seq_a[-1]]])[0])))
    np.testing.assert_array_equal(seq_a, ref_a)  # 1 + 4 + 1 = 6 new tokens
    # B continues decoding correctly after its chunked prefill
    groups.reset_topology()
    solo_b = InferenceEngineV2(model, params=params, max_batch=2,
                               max_seq_len=64, split_fuse_chunk=1024)
    ref_b = solo_b.generate([p_b], max_new_tokens=3)[0]
    seq_b = [*p_b, int(np.argmax(b_logits))]
    for _ in range(2):
        seq_b.append(int(np.argmax(both.put([1], [[seq_b[-1]]])[1])))
    np.testing.assert_array_equal(seq_b, np.asarray(ref_b))


def test_split_fuse_continuation_feed(tiny):
    """FastGen ragged semantics: a known uid can receive a multi-token feed
    (prefill continuation) — equivalent to having sent one longer prompt."""
    cfg, model, params = tiny
    rng = np.random.default_rng(4)
    prompt = list(rng.integers(0, cfg.vocab_size, 28))

    groups.reset_topology()
    ref_eng = InferenceEngineV2(model, params=params, max_batch=2,
                                max_seq_len=64)
    ref = ref_eng.put([0], [np.asarray(prompt, np.int32)])[0]

    groups.reset_topology()
    fed = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64,
                            split_fuse_chunk=8)
    first = fed.put([0], [np.asarray(prompt[:20], np.int32)])
    assert 0 not in first            # two rows of the width ran, 4 pending
    second = fed.put([], [])         # empty put drains the last chunk
    assert 0 in second               # first feed complete
    out = fed.put([0], [np.asarray(prompt[20:], np.int32)])[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_v2_sampling_seeded_and_diverse(tiny):
    """Sampled generation: deterministic per seed, different across seeds,
    eos honored (serving-surface version of ops/test_sampling.py)."""
    cfg, model, params = tiny
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=4, max_seq_len=64)
    prompts = [[5, 6, 7], [9, 10, 11]]
    a = v2.generate(prompts, max_new_tokens=8, temperature=0.9, top_k=50,
                    seed=3)
    b = v2.generate(prompts, max_new_tokens=8, temperature=0.9, top_k=50,
                    seed=3)
    c = v2.generate(prompts, max_new_tokens=8, temperature=0.9, top_k=50,
                    seed=4)
    assert a == b                      # same seed → same tokens
    assert a != c                      # different seed → different draw
    greedy = v2.generate(prompts, max_new_tokens=8)
    # outputs carry prompt + generated tokens (v1 generate() format)
    assert all(len(g) == len(pr) + 8 for g, pr in zip(greedy, prompts))
    # the sampling config must not leak into the greedy call
    again = v2.generate(prompts, max_new_tokens=8)
    assert greedy == again


def test_v2_prompt_longer_than_max_seq_fails_loudly(tiny):
    cfg, model, params = tiny
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=32)
    with pytest.raises(Exception) as ei:
        v2.generate([list(range(40))], max_new_tokens=4)
    msg = str(ei.value).lower()
    assert "seq" in msg or "32" in msg or "block" in msg


def test_generate_records_service_timing(tiny):
    """generate() must leave per-query SLA timestamps (admit <= first <=
    done, new_tokens = produced count) — what an effective-throughput
    row is computed from (reference fastgen README:163 accounting)."""
    cfg, model, params = tiny
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, 4 + i)) for i in range(5)]
    outs = v2.generate(prompts, max_new_tokens=5)
    assert set(v2.last_timing) == set(range(5))
    for uid, rec in v2.last_timing.items():
        assert 0.0 <= rec["admit"] <= rec["first"] <= rec["done"]
        assert rec["new_tokens"] == len(outs[uid]) - len(prompts[uid]) == 5


def test_v2_more_prompts_than_slots_all_complete(tiny):
    """Continuous batching admits waiting prompts as slots free (the core
    FastGen property) — all queries finish even at 3x oversubscription."""
    cfg, model, params = tiny
    groups.reset_topology()
    v2 = InferenceEngineV2(model, params=params, max_batch=2, max_seq_len=64)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, 1 + int(rng.integers(8))))
               for _ in range(6)]
    outs = v2.generate(prompts, max_new_tokens=6)
    assert len(outs) == 6
    assert all(len(o) == len(pr) + 6 for o, pr in zip(outs, prompts))
