"""Qwen3-Next (`models/qwen3_next.py`: Gated DeltaNet and gated full
attention three to one, `1 + w` norms, rotary over a quarter of a head, 32
softmax experts of which 8 are held beside a GATED shared one) against the
float32 reference (`perfbench/configs/qwen3_next_reference.py`, the delta
rule as the recurrence over positions), at a small size on seeded weights,
LOGITS not tokens: the plain forward, the loss, and a prefill and then
decoding through the matrix states AND the full-length rows: the questions
all eight hybrid families are asked, whose bodies are `hybrid_families.py`'s;
and this family's own: the chunked delta rule with a decay a head against the
recurrence, a (row, chunk) walk against a whole-row pass, the kernel's second
form, each line the configuration file lists under `assumed`, dropped; the
eight EP8 shares against the uncut layer; the counts against the tree and
the cache.

TOLERANCE (`hybrid_families.QWEN3_NEXT_TOL`, 5e-6 absolute on logits of
magnitude 0.7): program and reference both compute in float32 here, in
another order (the chunked solve against the positional recurrence, a
staged token against a written one, sorted expert rows against a dense sum,
a row's chunks against whole rows): the largest difference read is 9e-7. A
bf16 STATE moves the logits by 1e-3 (`test_a_bf16_state_would_not_pass`).
The real RATIOS at toy widths: two periods of three GDN layers to one full
one, key heads half the value heads, rotary over 4 of a head's 16, 32
experts top 4 of which 8 are held."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid, qwen3_next
from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
from deepspeed_tpu.ops.pallas import kda
from perfbench.manifest import Manifest
from tests.unit.models import hybrid_families
from tests.unit.models.hybrid_families import (QWEN3_NEXT_SIZES as SIZES,
                                               QWEN3_NEXT_TOL as TOL,
                                               compile_apply, family, walk)

F32 = jnp.float32
PUBLISHED_VOCAB = 151936       # rows of the embedding and of the head


@pytest.fixture(scope="module")
def served():
    fam = family("qwen3_next")
    return fam.model, fam.params, fam.ids, fam.want


def test_the_published_sizes_and_the_cache(served):
    cfg = Qwen3NextConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.rotary_dim, cfg.num_hidden_layers,
            cfg.vocab_size, cfg.rope_theta) == (2048, 16, 2, 256, 64, 48,
                                                PUBLISHED_VOCAB, 1e7)
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim, cfg.gdn_state_shape) == (
        2048, 4096, 8192, (32, 128, 128))
    # three GDN layers to one full, the full one every fourth
    assert cfg.kinds == "GGGA" * 12 and (cfg.gdn_layers, cfg.full_layers) == (
        36, 12)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.norm_topk_prob) == (512, 10, 512, 512, True)
    # a layer's kind follows its PUBLISHED index
    assert Qwen3NextConfig(num_hidden_layers=3,
                           published_layers=(2, 3, 4)).kinds == "GAG"
    with pytest.raises(ValueError, match="published_layers"):
        Qwen3NextConfig(num_hidden_layers=3, published_layers=(3, 2, 4))
    with pytest.raises(ValueError, match="whole groups"):
        Qwen3NextConfig(linear_num_key_heads=5)
    from deepspeed_tpu.inference.capacity_scan import (kv_bytes_by_kind,
                                                       kv_cache_bytes,
                                                       recurrent_state_bytes)
    token = 2 * 2 * 256 * 2         # K and V of a full layer, bf16
    assert kv_bytes_by_kind(cfg, 1, 1, jnp.bfloat16) == {
        "full_kv_bytes": 12 * token}
    assert kv_cache_bytes(cfg, 3, 4096, jnp.bfloat16) == 3 * 4096 * 12 * token
    # a sequence's state: 36 x (32 x 128 x 128 float32 + 3 x 8,192 bf16)
    assert recurrent_state_bytes(cfg, 1, jnp.bfloat16) == 36 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2)
    model = served[0]
    cache = model.make_cache(2, 128, dtype=jnp.bfloat16)
    assert cache.window is None and cache.latent is None \
        and cache.index_keys is None
    assert cache.kv.k.stack.shape == (2, 2, 2, 128, 16) and not cache.kv.ring
    assert cache.state.ssm.shape == (6, 2, 4, 8, 8) \
        and cache.state.ssm.dtype == F32
    assert cache.state.conv.shape == (6, 2, 3, 2 * 16 + 32)
    assert cache.max_len == 128 and cache.index.shape == (2,)
    kinds = model.cfg.kv_bytes_by_kind(2, 128, jnp.bfloat16)
    assert cache.kv.k.stack.nbytes + cache.kv.v.stack.nbytes \
        == kinds["full_kv_bytes"]
    assert cache.state.ssm.nbytes + cache.state.conv.nbytes \
        == model.cfg.recurrent_state_bytes(2, jnp.bfloat16)
    counts = Manifest().module("configs", "qwen3_next_counts")
    by_kind = counts.bytes_by_kind(SIZES, 2, 128)
    assert by_kind["full_kv_bytes"] == kinds["full_kv_bytes"]
    assert by_kind["state_bytes"] == cache.state.ssm.nbytes \
        == counts.gdn_update_bytes(SIZES, 2) // 2
    assert by_kind["conv_bytes"] == cache.state.conv.nbytes
    with pytest.raises(ValueError, match="int8"):
        model.make_cache(2, 128, quantized=True)


def test_the_counts_are_the_tree_s(served):
    counts = Manifest().module("configs", "qwen3_next_counts")
    assert counts.total_params(SIZES) == sum(
        x.size for x in jax.tree_util.tree_leaves(served[1]))
    layers = served[1]["layers"]
    assert {k for k in layers if k.startswith("layer_3")} == {
        "layer_3", "layer_3_norm", "layer_3_mlp_norm", "layer_3_mlp"}
    assert set(layers["layer_0"]) == {
        "in_proj_qkvz", "in_proj_ba", "conv_kernel", "A_log", "dt_bias",
        "norm_weight", "out_proj"}
    assert set(layers["layer_3"]) == {"q_proj", "k_proj", "v_proj", "o_proj",
                                      "q_norm", "k_norm"}
    # a head's query and its gate side by side: 4 heads x (16 + 16)
    assert layers["layer_3"]["q_proj"]["kernel"].shape == (64, 128)
    assert set(layers["layer_0_mlp"]) == {"gate", "experts", "shared_expert",
                                          "shared_expert_gate"}
    assert layers["layer_0_mlp"]["shared_expert_gate"].shape == (64, 1)
    assert set(layers["layer_0_mlp"]["gate"]) == {"wg"}
    assert counts._layers(SIZES) == (6, 2)


def test_what_the_family_shares_exists_once():
    """The chunked delta rule, the held-experts layer and the shell are
    `models/hybrid.py`'s (Ling's KDA and this family's GDN call ONE
    `delta_chunked`), the decode step ONE kernel under two names."""
    from deepspeed_tpu.models import ling_linear
    assert qwen3_next.hybrid is hybrid and ling_linear.hybrid is hybrid
    for module in (qwen3_next, ling_linear):
        assert not {"kda_chunked", "delta_chunked", "_neumann_inverse",
                    "_experts", "_RowGroups"} & set(vars(module))
    assert (kda.KERNEL_NAME, kda.HEAD_DECAY_NAME) == ("kda_state_update",
                                                      "gdn_state_update")


def test_the_plain_forward_is_the_reference_s():
    """The file's first use of the family pays its one build (seeded
    weights, the reference's op-by-op float32 forward)."""
    hybrid_families.the_plain_forward_is_the_reference_s("qwen3_next")


def test_the_loss_is_the_reference_s():
    hybrid_families.the_loss_is_the_reference_s("qwen3_next")


@pytest.mark.parametrize("prompt", [20], ids=["twenty"])
def test_prefill_then_decode_through_states_and_full_rows(prompt):
    """The reference's full forward against a prefill (the chunked form
    into the stored states, the full layers' rows written) and a decode step
    a position (the state updated in place, the token staged and landed) in
    LOGITS at every decoded position."""
    hybrid_families.prefill_then_decode_is_the_reference_s("qwen3_next",
                                                           prompt)


def test_a_prefill_walks_a_row_a_chunk_at_a_time(served, monkeypatch):
    """`PREFILL_CHUNK` 8 under a prompt of 24: three chunks a row, each
    continued from the row's stored state and convolution tail and attended
    against the row's written K and V so far, equal to the whole-row pass;
    the counters come out of the scan."""
    model, params, ids, want = served
    whole = compile_apply()(model, params, ids[:, :24],
                            model.make_cache(3, 64, dtype=F32))
    monkeypatch.setattr(qwen3_next, "PREFILL_CHUNK", 8)
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, :24], model.make_cache(3, 64, dtype=F32))
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 23],
                               atol=TOL)
    # the chunked walk against the one-chunk walk: the states and the rows
    # (values of magnitude 2, float32 sums in another grouping: read 5e-6)
    for got, one in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(whole[1])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(one),
                                   atol=2e-5)
    assert np.array_equal(np.asarray(cache.index), [24] * 3)

    def sums(counted):
        return {name: sum(
            int(jnp.sum(v)) for path, v in
            jax.tree_util.tree_leaves_with_path(counted["counters"])
            if path[-1].key == name) for name in model.program_counters}
    got = sums(counted)
    assert got["delta_prefill_positions"] == 6 * 3 * 24     # six GDN layers
    assert got["assignments"] == 8 * 3 * 24 * 4
    assert 0 < got["held_assignments"] < got["assignments"]
    assert got["state_updates"] == got["kv_positions_attended"] == 0
    (logits, cache), counted = compile_apply(mutable=["counters"])(
        model, params, ids[:, 24:25], cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, 24],
                               atol=TOL)
    got = sums(counted)
    assert got["state_updates"] == 6 * 3
    assert got["kv_positions_attended"] == 2 * 3 * 25   # two full layers
    assert got["delta_prefill_positions"] == 0


def test_the_chunk_divides_the_prompt():
    """A recurrent layer cannot walk a position twice: no chunk is drawn
    back. Whole 128-query tiles where the length has them; a length with no
    divisor of 64 or more under the budget walks its rows whole."""
    assert hybrid.dividing_chunk(32768, 2048) == 2048
    assert hybrid.dividing_chunk(2176, 2048) == 128      # 17 x 128
    assert hybrid.dividing_chunk(300, 2048) == 300
    assert hybrid.dividing_chunk(5000, 2048) == 1250
    assert hybrid.dividing_chunk(4099, 2048) == 4099     # a prime
    assert hybrid.dividing_chunk(40, 8) == 8
    for s, chunk in ((32768, 2048), (5000, 1250), (300, 300), (40, 8)):
        size, starts = hybrid.prefill_chunks(s, chunk)
        assert size == chunk and starts == list(range(0, s, chunk))
    # the other families' rule stands: whole tiles, the last one drawn back
    assert hybrid.prefill_chunks(300, 2048) == (256, [0, 44])


def _delta_operands(key, b, s, h, dk, dv, head):
    ks = jax.random.split(key, 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    g_shape = (b, s, h) if head else (b, s, h, dk)
    return (unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, dk))),
            jax.random.normal(ks[2], (b, s, h, dv)),
            -2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], g_shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
            jax.random.normal(ks[5], (b, h, dk, dv)))


def _recurrence(q, k, v, g, beta, s0):
    """The delta rule a position at a time (`kda.kda_step`)."""
    def step(s, t):
        o, s = kda.kda_step(s, *t)
        return s, o
    last, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


@pytest.mark.parametrize("chunk", [64, 8])
def test_the_chunked_form_is_the_recurrence_with_a_decay_a_head(chunk):
    """`hybrid.delta_chunked` with `g` (B, S, H) against the recurrence, at a
    sequence that is no multiple of the block and from a NON-ZERO state:
    float32 both, 1e-5 of values of magnitude 1 (the triangular solve by
    products against a position at a time; read 2e-6). The same numbers as
    the decay-a-channel form handed the decay broadcast."""
    ops = _delta_operands(jax.random.PRNGKey(5), 2, 77, 3, 16, 8, head=True)
    o, last = jax.jit(hybrid.delta_chunked, static_argnums=6)(*ops, chunk)
    want_o, want_last = jax.jit(_recurrence)(*ops)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(last, want_last, atol=1e-5)
    q, k, v, g, beta, s0 = ops
    by_channel = jax.jit(hybrid.delta_chunked, static_argnums=6)(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, s0,
        min(chunk, 32))
    np.testing.assert_allclose(o, by_channel[0], atol=1e-5)
    np.testing.assert_allclose(last, by_channel[1], atol=1e-5)


def test_a_strong_decay_neither_overflows_nor_underflows():
    """A decay a head of e^-20 a step (A at 16, the gate open): every
    exponent of the head form is of a non-positive number."""
    q, k, v, g, beta, s0 = _delta_operands(jax.random.PRNGKey(6), 1, 130, 2,
                                           16, 8, head=True)
    g = jnp.full_like(g, -20.0).at[:, ::7].set(-1e-3)
    o, last = jax.jit(hybrid.delta_chunked, static_argnums=6)(
        q, k, v, g, beta, s0, 64)
    want_o, want_last = jax.jit(_recurrence)(q, k, v, g, beta, s0)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(last)))
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(last, want_last, atol=1e-5)


# `jax.make_jaxpr` of `kda_state_update` with a decay a CHANNEL at the shapes
# below, as the parent commit (PR 63) traced it: sha256 of its text
PARENT_KDA_JAXPR = \
    "10791c4660a76f4961994e79cdb88dc332893b5ad75d2cd0e97eeb47929155cb"


def test_the_kernel_s_second_form_and_its_first():
    """`gdn_state_update` (interpret mode: `g` (B, H), a decay a head) against
    `kda_step`, and against the first form handed the decay broadcast: the
    same float32 products and sums in the same order (1e-6: read 0). And
    `kda_state_update` with a decay a channel is still the PARENT's program:
    its jaxpr is the one PR 63 traced, to the character."""
    q, k, v, g, beta, _ = _delta_operands(jax.random.PRNGKey(7), 3, 1, 4, 16,
                                          16, head=True)
    q, k, v, g, beta = (t[:, 0] for t in (q, k, v, g, beta))
    state = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 4, 16, 16))
    o, new = jax.jit(lambda *a: kda.kda_state_update(
        a[0], 1, *a[1:], interpret=True))(state, q, k, v, g, beta)
    want_o, want_s = kda.kda_step(state[1], q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(new[1], want_s, atol=1e-6)
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    wide = jnp.broadcast_to(g[..., None], q.shape)
    o2, new2 = jax.jit(lambda *a: kda.kda_state_update(
        a[0], 1, *a[1:], interpret=True))(state, q, k, v, wide, beta)
    np.testing.assert_allclose(o, o2, atol=1e-6)
    np.testing.assert_allclose(new, new2, atol=1e-6)
    ref_o, ref_s = kda.kda_state_update_reference(state, 1, q, k, v, g, beta)
    np.testing.assert_allclose(o, ref_o, atol=1e-6)
    np.testing.assert_allclose(new, ref_s, atol=1e-6)
    zeros = (jnp.zeros((2, 3, 4, 16, 16), F32),) + (
        jnp.zeros((3, 4, 16), F32),) * 4 + (jnp.zeros((3, 4), F32),)
    text = str(jax.make_jaxpr(lambda s, *a: kda.kda_state_update(
        s, 1, *a, interpret=True))(*zeros))
    assert "gdn_state_update" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_KDA_JAXPR
    with pytest.raises(ValueError, match="kda_state_update"):
        kda.kda_state_update(state, 1, q, k, v, g[:, :2], beta)


def test_a_bf16_state_would_not_pass(served):
    """The state rounded to bf16 between decode steps (float32 inside a
    step): the logits at the decoded positions leave the reference's by more
    than twenty times the tolerance."""
    model, params, ids, want = served
    got, _ = walk(model, params, ids, 20, 64, state_bits=7)
    assert not np.all(np.abs(np.asarray(got) - want[:, 19:]) <= 20 * TOL)


def _ungated(x, p):
    ref, sh = family("qwen3_next").reference, p["shared_expert"]
    return ref._swiglu(x, sh["gate"][0], sh["up"][0], sh["down"][0])


# name -> (the file's keys changed, (a function of the reference, what
# replaces it given the real one)): the reference WITH the fault. Each (a)
# of ISSUE 64's equations is here, dropped.
FAULTS = {
    "the shared expert's gate dropped": ({}, ("_shared", lambda real: lambda
                                              x, p: _ungated(x, p))),
    "the 1 + of a norm dropped": ({}, ("_norm", lambda real: lambda x, w, eps:
                                       real(x, w - 1.0, eps))),
    "the 1 + of a head norm dropped": ({}, (
        "_head_norm", lambda real: lambda x, w, eps: real(x, w - 1.0, eps))),
    "rotary over the whole head": ({"partial_rotary_factor": 1.0}, None),
    "the q / k l2 norm dropped": ({}, ("_l2", lambda real: lambda x: x)),
    "exp(g) applied after the correction": ({}, (
        "_decayed", lambda real: lambda s, u_of, g:
        (s + u_of(s)) * jnp.exp(g)[:, None, None])),
    "silu(z) as sigmoid(z)": ({}, ("_z_gate", lambda real: jax.nn.sigmoid)),
    "the top-k weights not renormalised": ({"norm_topk_prob": False}, None),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_program_with_a_fault_would_not_pass(served, fault, monkeypatch):
    """The reference WITH the fault lies further from the program than
    twenty times the tolerance the program is held to: the comparisons above
    would refuse a program that had it."""
    _, params, ids, want = served
    fam = family("qwen3_next")
    sizes, patch = FAULTS[fault]
    if patch:
        name, replacement = patch
        monkeypatch.setattr(fam.reference, name,
                            replacement(getattr(fam.reference, name)))
    # ONE row: the op-by-op float32 reference is seconds a row
    other = fam.reference_logits(params, ids[:1], {**SIZES, **sizes})
    assert not np.all(np.abs(other - want[:1]) <= 20 * TOL)     # NaN is far


def test_a_reference_with_a_bf16_state_would_not_pass(served, monkeypatch):
    """And the reference's own recurrence with its state kept in bf16."""
    _, params, ids, want = served
    ref = family("qwen3_next").reference
    real = ref._gdn
    monkeypatch.setattr(ref, "_gdn", lambda *a: real(*a,
                                                     state_dtype=jnp.bfloat16))
    other = family("qwen3_next").reference_logits(params, ids[:1], SIZES)
    assert not np.all(np.abs(other - want[:1]) <= 20 * TOL)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's tie of the share to the model: an expert
    layer that holds all 32 experts against the eight EP8 shares of it (4
    experts each), their routed parts summed, and the GATED shared expert
    counted once. What every chip computes alike (the softmax over all 32
    logits, the choice of the 4 best, the taken weights' sum) is inside each
    share once."""
    from deepspeed_tpu.moe.layer import MoE
    kw = dict(hidden_size=64, num_experts=32, k=4, intermediate_size=32,
              norm_topk_prob=True, drop_tokens=False, dtype=F32,
              activation="silu", dispatch_impl="ragged", score_fn="softmax")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64))
    whole = MoE(**kw, held_offset=0, held_experts=32,
                shared_intermediate_size=32, shared_gate=True)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(8), x,
                                      train=False))["params"]
    assert set(params) == {"gate", "experts", "shared_expert",
                           "shared_expert_gate"}
    params["gate"]["wg"] = params["gate"]["wg"] * 40.0     # a decided router
    params["shared_expert_gate"] = params["shared_expert_gate"] * 40.0
    want = whole.apply({"params": params}, x, train=False)

    def share(chip, shared):
        part = {"gate": params["gate"], "experts": jax.tree_util.tree_map(
            lambda t: t[4 * chip:4 * chip + 4], params["experts"])}
        if shared:
            part.update(shared_expert=params["shared_expert"],
                        shared_expert_gate=params["shared_expert_gate"])
        return MoE(**kw, held_offset=4 * chip, held_experts=4,
                   shared_intermediate_size=32 if shared else None,
                   shared_gate=shared).apply({"params": part}, x, train=False)

    routed = [share(chip, shared=False) for chip in range(8)]
    once = share(0, shared=True) - routed[0]            # the shared expert
    np.testing.assert_allclose(sum(routed) + once, want, atol=2e-5)
    assert sum(float(jnp.abs(r).max()) > 0 for r in routed) > 4
    # without its gate the shared expert is another number
    bare = MoE(**kw, held_offset=0, held_experts=4,
               shared_intermediate_size=32).apply({"params": {
                   "gate": params["gate"], "shared_expert":
                   params["shared_expert"], "experts": jax.tree_util.tree_map(
                       lambda t: t[:4], params["experts"])}}, x, train=False)
    assert float(jnp.abs(bare - routed[0] - once).max()) > 1e-3
    # and the reference's layer, given the whole, says the same
    sizes = {**SIZES, "num_experts": 32, "router_experts": 32,
             "expert_offset": 0, "num_experts_per_tok": 4}
    ref = family("qwen3_next").reference
    with jax.default_matmul_precision("highest"):
        ref_out, margin = ref._experts(x.reshape(24, 64), params, sizes)
        _, cut = ref._experts(x.reshape(24, 64), {
            **params, "experts": jax.tree_util.tree_map(
                lambda t: t[:4], params["experts"])},
            {**sizes, "num_experts": 4})
    np.testing.assert_allclose(ref_out.reshape(want.shape), want, atol=2e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    # a share's margin is taken over its held experts alone: never smaller
    assert bool(jnp.all(cut >= margin - 1e-6))
