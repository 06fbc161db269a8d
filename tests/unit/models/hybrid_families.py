"""What the tests of the eight hybrid families share (`test_nemotron_h.py`,
`test_phi4flash.py`, `test_ling_linear.py`, `test_keye_sparse.py`,
`test_deepseek_sparse.py`, `test_openpangu.py`, `test_afmoe.py`,
`test_qwen3_next.py`): each
family at a small size on seeded weights with the benchmark's plain float32
reference beside it, the model's `apply` under ONE `jax.jit`, the walk
through the caches, and the questions asked of all six alike, written once (`the_plain_forward_...`,
`the_loss_...`, `prefill_then_decode_...`: LOGITS not tokens, each family
held to its own tolerance in its own way, `Family.close`). Each family's
file asks them under its own test names: a file is one worker's under
`--dist loadfile`, and a family's build (seeded weights and the reference's
op-by-op float32 forward: 20 s alone, 50 s beside five busy workers; a
jitted reference is 3e-6 from the eager one, most of Ling-linear's 5e-6)
is paid once a process (`family(name)`), so a file of the shared questions
alone paid every build a second time (PR 50 tried it: 150 s).

Nothing here is mutated by a test.
"""

import dataclasses
import functools
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import (afmoe, deepseek_sparse, keye_sparse,
                                  ling_linear, nemotron_h, openpangu,
                                  phi4flash, qwen3_next)
from perfbench.manifest import Manifest


def compile_apply(**kw):
    """`model.apply` under a `jax.jit` of its own: the plain forward, a
    prefill of each length and the decode step compile once a (model, shape).
    Op by op, every interpreted kernel compiled anew at every call (a walk
    of a prefill and 24 steps took a minute)."""
    def apply(model, params, ids, cache=None):
        if cache is None:
            return model.apply({"params": params}, ids, **kw)
        return model.apply({"params": params}, ids, cache=cache, **kw)
    return jax.jit(apply, static_argnames=("model",))


# the process's. A test that patches a module constant (`PREFILL_TOKENS`)
# takes its own (`compile_apply()`: a new function, so a new trace): this
# one's traces must not answer it, nor keep what it traced.
compiled = compile_apply()


def walk(model, params, ids, prompt, cache_len, state_bits=None,
         apply=compiled):
    """A prefill of `prompt` positions, then one decode step a position
    through the model's own cache, teacher-forced: the logits they gave side
    by side (every position where the family's prefill gives all, from
    `prompt - 1` on where it gives the last alone), and the cache.
    `state_bits`: what a lower-precision recurrent state would keep between
    steps (float32 arithmetic inside a step, as a kernel would do it)."""
    cache = model.make_cache(ids.shape[0], cache_len, dtype=jnp.float32)
    out, cache = apply(model, params, ids[:, :prompt], cache)
    outs = [out]
    for t in range(prompt, ids.shape[1]):
        if state_bits is not None:
            cache = cache.replace(state=cache.state.replace(
                ssm=jax.lax.reduce_precision(cache.state.ssm, 8, state_bits)))
        out, cache = apply(model, params, ids[:, t:t + 1], cache)
        outs.append(out)
    assert np.array_equal(np.asarray(cache.index),
                          [ids.shape[1]] * ids.shape[0])
    return jnp.concatenate(outs, axis=1), cache


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def moved(params, below, but=None):
    """The seeded tree with its small parameters (fewer than `below`
    values; none whose path holds `but`) moved off their initial values
    (biases 0, norm weights 1, D 1, a selection bias of 0.01): a term the
    program dropped, or took from the wrong layer of a stack, would
    otherwise not show."""
    def bump(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(7),
                                 zlib.crc32(name.encode()) % 2 ** 31)
        small = x.size < below and not (but and but in name)
        return x + 0.1 * jax.random.normal(key, x.shape, x.dtype) if small \
            else x
    return jax.tree_util.tree_map_with_path(bump, params)


@dataclasses.dataclass(frozen=True)
class Family:
    reference: Any              # `perfbench/configs/<name>_reference.py`
    cfg: Any
    sizes: dict                 # what the reference takes for `cfg`
    model: Any
    params: Any
    ids: jax.Array
    want: np.ndarray            # the reference's logits, every position
    cache_len: int
    reference_logits: Callable  # (params, ids, sizes) -> every position's
    close: Callable             # asserts (got, want) within the family's tolerance, its way
    loss_fn: Callable


# Tolerances. Program and reference both compute in float32 here, in another
# order (a chunked scan or solve against the positional recurrence, sorted
# expert rows against a dense sum, absorbed against expanded products):
# nothing else. Each leaves room for that and none for a dropped term (the
# families' own files hold the mutations to fifty or twenty times it).
NEMOTRON_TOL = 2e-5     # RELATIVE to the largest logit (magnitude 0.5: 1e-6)
PHI4_TOL = 2e-5
LING_TOL = 5e-6         # read 6e-7
KEYE_TOL = 3e-6         # read 4e-7
DEEPSEEK_TOL = 5e-6
OPENPANGU_TOL = 5e-6    # read 4e-7: absorbed against expanded, sorted rows
AFMOE_TOL = 5e-6        # read 5e-7: rings and staged tokens against whole rows
QWEN3_NEXT_TOL = 5e-6   # read 9e-7: the chunked solve against the recurrence

NEMOTRON_CFG = nemotron_h.NemotronHConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=6,
    hybrid_override_pattern="ME*MEE", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=4,
    router_experts=8, expert_offset=2, num_experts_per_tok=3,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    dtype=jnp.float32, dispatch_impl="gmm")
PHI4_SIZES = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=8, num_attention_heads=8,
                  num_key_value_heads=4, sliding_window=8,
                  layer_norm_eps=1e-5)
LING_SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=6,
    intermediate_size=96, first_k_dense_replace=1, layer_group_size=6,
    published_layers=(0, 2, 3, 4, 5, 6), num_attention_heads=4, head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5.0, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=6e6,
    num_experts=8, router_experts=16, expert_offset=0, num_experts_per_tok=4,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=4, topk_group=2,
    rms_norm_eps=1e-6)
# the file's keys, as the reference and the adapter read them: 8 positions
# chosen of up to 40, experts 2-5 of 8 held
KEYE_SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=1e7, rope_scaling={"rope_type": "default"},
    num_experts=4, num_local_experts=4, router_experts=8, expert_offset=2,
    num_experts_per_tok=3, moe_intermediate_size=32, norm_topk_prob=True,
    rms_norm_eps=1e-6, max_position_embeddings=4096,
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                   indexer_num_kv_heads=1, topk=8))
# the file's keys, as the reference and the adapter read them: 8 positions
# chosen of up to 40, a dense layer and two expert layers, experts 4-7 of 16
# held: the second half of group 0 and the first half of group 1 of 4 groups
DEEPSEEK_SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=3, intermediate_size=96,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=10000.0, index_n_heads=4, index_head_dim=16,
    index_topk=8, n_routed_experts=4, router_experts=16, expert_offset=6,
    num_experts_per_tok=4, moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=4, topk_group=2,
    router_bias_scale=0.01, rms_norm_eps=1e-6, max_position_embeddings=4096,
    num_nextn_predict_layers=0,
    # stretched from an original context of 16: of the 4 rotary pairs one
    # keeps its frequency, one is divided by 40, two lie on the ramp, inside
    # the tests' 40 positions
    rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16))
# the file's keys, as the reference and the adapter read them: a dense layer
# and two expert layers, four norms each; experts 6-9 of 16 held, the 4 best
# of all 16 taken at once (no groups, no selection bias)
OPENPANGU_SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=3, intermediate_size=96,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=25600000.0, n_routed_experts=4,
    router_experts=16, expert_offset=6, num_experts_per_tok=4,
    moe_intermediate_size=32, n_shared_experts=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-5, max_position_embeddings=4096,
    num_nextn_predict_layers=0, sandwich_norm=True)
# the file's keys, as the reference and the adapter read them: a dense layer
# and four expert layers, three window layers (8 positions, with rotary)
# among two full ones (no position), so each kind's stack has several slots
# and neither kind's are contiguous; experts 6-9 of 16 held, the 4 best of
# all 16 biased scores taken at once
AFMOE_SIZES = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, global_attn_every_n_layers=4,
    layer_types=["sliding_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    sliding_window=8, rope_theta=10000.0, num_experts=4, router_experts=16,
    expert_offset=6, num_experts_per_tok=4, moe_intermediate_size=32,
    num_shared_experts=1, route_norm=True, route_scale=2.826,
    mup_enabled=True, rms_norm_eps=1e-5, max_position_embeddings=4096,
    window_layers=3, full_layers=2)
# the file's keys, as the reference and the adapter read them: the published
# RATIOS at toy widths. Two periods of three GDN layers to one full layer
# (published layers 0-7); key heads half the value heads; rotary over a
# quarter of a head; 32 experts, top 4, experts 8-15 held; the shared
# expert gated
QWEN3_NEXT_SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=8,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_conv_kernel_dim=4, linear_key_head_dim=8, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_value_head_dim=8, num_experts=8,
    router_experts=32, expert_offset=8, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, rms_norm_eps=1e-6, max_position_embeddings=4096,
    full_layers=2, gdn_layers=6)
PHI4_CFG = phi4flash.Phi4FlashConfig(**PHI4_SIZES, dtype=jnp.float32)
LING_CFG = ling_linear.LingLinearConfig(**LING_SIZES, dtype=jnp.float32)


def _nemotron_h():
    cfg, ref = NEMOTRON_CFG, Manifest().module("configs",
                                               "nemotron_h_reference")
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
             if f.name != "dtype"}
    model, params = nemotron_h.materialize_params(cfg, jax.random.PRNGKey(3))
    # random init barely uses the recurrence (its output is a hundredth of
    # the skip term's): slow the decay and raise dt, so that the state
    # carries hundreds of positions and a fault in it shows in the logits
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        if kind == "M":
            layer = params["layers"][f"layer_{i}"]
            layer["A_log"] = jnp.full_like(layer["A_log"], -4.0)
            layer["dt_bias"] = jnp.full_like(layer["dt_bias"], 1.0)
            layer["D"] = jnp.zeros_like(layer["D"])
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 29), 0,
                             cfg.vocab_size)

    def reference_logits(params, ids, sizes=sizes):
        h = ref.hidden_states(params, ids, sizes)
        with jax.default_matmul_precision("highest"):
            return h @ params["lm_head"]

    def close(got, want):
        assert rel(got, want) < NEMOTRON_TOL
    return Family(ref, cfg, sizes, model, params, ids,
                  np.asarray(reference_logits(params, ids)), 64,
                  reference_logits, close, nemotron_h.nemotron_h_loss_fn)


def _phi4flash():
    ref = Manifest().module("configs", "phi4flash_reference")
    cfg = PHI4_CFG
    model, params = phi4flash.materialize_params(cfg, jax.random.PRNGKey(0))
    params = moved(params, 5000)
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, 30), 1, 128)

    def reference_logits(params, ids, sizes=PHI4_SIZES):
        return ref._head(ref.hidden_states(params, ids, sizes), params)

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want, atol=PHI4_TOL)
    return Family(ref, cfg, PHI4_SIZES, model, params, ids,
                  np.asarray(reference_logits(params, ids)), 32,
                  reference_logits, close, phi4flash.phi4flash_loss_fn)


def _ling_linear():
    ref = Manifest().module("configs", "ling_linear_reference")
    cfg = LING_CFG
    model, params = ling_linear.materialize_params(cfg, jax.random.PRNGKey(0))
    params = moved(params, 3000, but="A_log")
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, 50), 1, 128)

    def reference_logits(params, ids, sizes=LING_SIZES):
        return np.asarray(ref.logits_at(params, ids,
                                        list(range(ids.shape[1])), sizes))

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want, atol=LING_TOL)
    return Family(ref, cfg, LING_SIZES, model, params, ids,
                  reference_logits(params, ids), 128,
                  reference_logits, close, ling_linear.ling_linear_loss_fn)


def _from_adapter(name, module, sizes, tol, loss_fn, spread):
    """A family built through its adapter (`<name>_adapter`,
    `<name>_reference` under `perfbench/configs/`): the small parameters off
    their initial values (the norms' weights, an index key's LayerNorm:
    weight 1, bias 0), and the kernels whose path `spread` names at 20 x
    their seeded range, where what they decide really decides (as seeded
    every index score, and every router's logit, is near 0)."""
    manifest = Manifest()
    ref = manifest.module("configs", name + "_reference")
    cfg = manifest.module("configs", name + "_adapter").model_config(
        sizes, dtype=jnp.float32, dispatch_impl="gmm")
    model, params = module.materialize_params(cfg, jax.random.PRNGKey(0))
    params = moved(params, 600)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 20.0 if spread(jax.tree_util.keystr(path)) else x,
        params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 1, 128)

    def reference_logits(params, ids, sizes=sizes):
        return np.asarray(ref.logits_at(params, ids,
                                        list(range(ids.shape[1])), sizes))

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want, atol=tol)
    return Family(ref, cfg, sizes, model, params, ids,
                  reference_logits(params, ids), 64, reference_logits, close,
                  loss_fn)


def _index_kernels(path):
    """A learned choice's index projections."""
    return "index_" in path and "kernel" in path


def _keye_sparse():
    return _from_adapter("keye_sparse", keye_sparse, KEYE_SIZES, KEYE_TOL,
                         keye_sparse.keye_sparse_loss_fn, _index_kernels)


def _deepseek_sparse():
    return _from_adapter("deepseek_sparse", deepseek_sparse, DEEPSEEK_SIZES,
                         DEEPSEEK_TOL,
                         deepseek_sparse.deepseek_sparse_loss_fn,
                         _index_kernels)


def _openpangu():
    """The FOUR norms a layer and the two compressions' off their seeded 1;
    the routers at a range at which the ungrouped choice decides."""
    return _from_adapter("openpangu", openpangu, OPENPANGU_SIZES,
                         OPENPANGU_TOL, openpangu.openpangu_loss_fn,
                         lambda path: path.endswith("['gate']['wg']"))


def _afmoe():
    """The four norms a layer, the head norms and the selection bias off
    their seeded values; the routers at a range at which the choice
    decides. 40 positions under a window of 8: every ring wraps in the
    prefill and again in decode."""
    return _from_adapter("afmoe", afmoe, AFMOE_SIZES, AFMOE_TOL,
                         afmoe.afmoe_loss_fn,
                         lambda path: path.endswith("['gate']['wg']"))


def _qwen3_next():
    """The norms' `w`, the head norms, `A_log` / `dt_bias`, the shared
    expert's gate and the convolution off their seeded values; the routers
    at a range at which the choice decides."""
    return _from_adapter("qwen3_next", qwen3_next, QWEN3_NEXT_SIZES,
                         QWEN3_NEXT_TOL, qwen3_next.qwen3_next_loss_fn,
                         lambda path: path.endswith("['gate']['wg']"))


FAMILIES = {"deepseek_sparse": _deepseek_sparse, "openpangu": _openpangu, "nemotron_h": _nemotron_h, "phi4flash": _phi4flash,
            "ling_linear": _ling_linear, "keye_sparse": _keye_sparse,
            "afmoe": _afmoe, "qwen3_next": _qwen3_next}


@functools.cache
def family(name) -> Family:
    return FAMILIES[name]()


@functools.cache
def walked(name, prompt):
    """`walk` of the family as it stands from a prefill of `prompt`, which
    several tests start from."""
    fam = family(name)
    return walk(fam.model, fam.params, fam.ids, prompt, fam.cache_len)


# ------------------------------------------ the questions asked of all three


def the_plain_forward_is_the_reference_s(name):
    """float32 both: the orders of summation differ, nothing else."""
    fam = family(name)
    fam.close(compiled(fam.model, fam.params, fam.ids), fam.want)


def the_loss_is_the_reference_s(name):
    fam = family(name)
    loss = jax.jit(fam.loss_fn(fam.model))(fam.params,
                                           {"input_ids": fam.ids}, None)
    assert float(loss) == pytest.approx(
        float(fam.reference.mean_loss(fam.params, fam.ids, fam.sizes)),
        rel=1e-5)


def prefill_then_decode_is_the_reference_s(name, prompt, dispatch=None):
    """A prefill of `prompt` positions, then a decode step a position
    through the family's own caches, teacher-forced, against the reference's
    logits at the same positions (the walk checks the cursors at its end);
    `dispatch`: under another dispatch of the experts than the family's."""
    fam = family(name)
    if dispatch is None or dispatch == fam.cfg.dispatch_impl:
        got, cache = walked(name, prompt)
    else:
        model = type(fam.model)(dataclasses.replace(fam.cfg,
                                                    dispatch_impl=dispatch))
        got, cache = walk(model, fam.params, fam.ids, prompt, fam.cache_len)
    # a prefill that gives the last position's logits alone gives one
    length = fam.ids.shape[1]
    assert got.shape[1] in (length, length - prompt + 1)
    fam.close(got, fam.want[:, length - got.shape[1]:])
    if cache.window is not None:        # the rings' cursor is the cache's
        assert np.array_equal(np.asarray(cache.window.index), cache.index)
