"""HF checkpoint import golden tests: build a tiny HF model with
transformers (torch CPU), save it, load through
`module_inject.load_hf_checkpoint`, and require logits parity.

Mirrors the reference's kernel-injection correctness tests
(tests/unit/inference — HF model vs injected model output comparison)."""

import numpy as np
import pytest
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def assert_greedy_equivalent(hf_model, prompt, out, atol=1e-3):
    """Cross-framework greedy parity, robust to argmax ties: every generated
    token must be within `atol` of HF's best logit at that step (an exact
    match is a special case; a real bug shows a large margin)."""
    full = torch.tensor(np.asarray(out)[None] if np.asarray(out).ndim == 1
                        else np.asarray(out))
    with torch.no_grad():
        logits = hf_model(full).logits.float().numpy()
    p = len(prompt)
    for t in range(p, full.shape[1]):
        step = logits[0, t - 1]
        margin = step.max() - step[int(full[0, t])]
        assert margin < atol, (t, margin)


def _logits_parity(hf_model, tmp_path, rtol=2e-3, atol=2e-3, vocab=128,
                   tie_tolerant=False, config=None):
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32,
                                       config=config)

    ids = np.random.default_rng(0).integers(0, vocab, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.float().numpy()
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids, jnp.int32)))
    if tie_tolerant:
        # MoE: near-tied gate logits can flip a token's expert between
        # implementations (fp reduction order), perturbing that token's
        # logits — require bulk agreement instead of elementwise
        close = np.isclose(ref, got, rtol=rtol, atol=atol)
        assert close.mean() > 0.99, f"only {close.mean():.4f} of logits match"
    else:
        np.testing.assert_allclose(ref, got, rtol=rtol, atol=atol)
    return model, params


def test_llama_import(tmp_path):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, attn_implementation="eager")
    _logits_parity(transformers.LlamaForCausalLM(cfg), tmp_path)


def test_llama_tied_embeddings_import(tmp_path):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        tie_word_embeddings=True, attn_implementation="eager")
    _logits_parity(transformers.LlamaForCausalLM(cfg), tmp_path)


def test_gpt2_import(tmp_path):
    cfg = transformers.GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        attn_implementation="eager")
    _logits_parity(transformers.GPT2LMHeadModel(cfg), tmp_path)


def test_mixtral_import(tmp_path):
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, attn_implementation="eager")
    # compare the math, not capacity-drop routing: HF never drops tokens,
    # so disable drops via a huge capacity; near-tied gates may still flip
    # an expert between implementations → tie_tolerant bulk comparison
    import dataclasses
    from deepspeed_tpu.module_inject import from_hf_config
    hf = transformers.MixtralForCausalLM(cfg)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    zoo_cfg = dataclasses.replace(from_hf_config(str(tmp_path)),
                                  capacity_factor=100.0, dtype=jnp.float32)
    model, params = _logits_parity(hf, tmp_path, rtol=5e-3, atol=5e-3,
                                   tie_tolerant=True, config=zoo_cfg)


def test_generate_from_hf_weights(tmp_path):
    """End-to-end: HF weights → init_inference → generate (greedy parity
    with transformers.generate)."""
    import deepspeed_tpu
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, attn_implementation="eager")
    hf = transformers.LlamaForCausalLM(cfg).eval()
    hf.save_pretrained(tmp_path, safe_serialization=True)

    from deepspeed_tpu.module_inject import load_hf_checkpoint
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    engine = deepspeed_tpu.init_inference(model, params=params, dtype="fp32")

    ids = np.random.default_rng(1).integers(0, 128, (1, 8))
    out = engine.generate(ids, max_new_tokens=8)
    assert_greedy_equivalent(hf, ids[0], out[0])


def test_qwen2_import(tmp_path):
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        attn_implementation="eager")
    _logits_parity(transformers.Qwen2ForCausalLM(cfg), tmp_path)


def test_qwen2_tied_import_and_generate(tmp_path):
    """Qwen2's small checkpoints tie embeddings; greedy decode must track HF."""
    import jax.numpy as jnp
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True,
        attn_implementation="eager")
    hf = transformers.Qwen2ForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = [3, 17, 9, 44]
    out = eng.generate(np.asarray([prompt]), max_new_tokens=8)[0]
    assert_greedy_equivalent(hf, prompt, out)


def test_mistral_import(tmp_path):
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, sliding_window=None,
        attn_implementation="eager")
    _logits_parity(transformers.MistralForCausalLM(cfg), tmp_path)


def test_mistral_sliding_window_import(tmp_path):
    """HF eager Mistral applies the sliding-window mask — parity must hold
    with the window ACTIVE (seq 10 > window 4)."""
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, sliding_window=4,
        attn_implementation="eager")
    _logits_parity(transformers.MistralForCausalLM(cfg), tmp_path)


def test_phi_import(tmp_path):
    cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=128,
        attn_implementation="eager")
    _logits_parity(transformers.PhiForCausalLM(cfg), tmp_path)


def test_falcon_import_and_generate(tmp_path):
    import jax.numpy as jnp
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, alibi=False, bias=False,
        attn_implementation="eager")
    hf = transformers.FalconForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = [3, 17, 9, 44]
    out = eng.generate(np.asarray([prompt]), max_new_tokens=8)[0]
    assert_greedy_equivalent(hf, prompt, out)


def test_falcon_mha_interleaved_import(tmp_path):
    """multi_query=False classic Falcon fuses QKV per-head interleaved —
    the converter must de-interleave, not block-split."""
    cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=False, parallel_attn=True,
        new_decoder_architecture=False, alibi=False, bias=False,
        attn_implementation="eager")
    _logits_parity(transformers.FalconForCausalLM(cfg), tmp_path)


def test_bloom_import_and_generate(tmp_path):
    import jax.numpy as jnp
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        attn_implementation="eager")
    hf = transformers.BloomForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = [3, 17, 9, 44]
    out = eng.generate(np.asarray([prompt]), max_new_tokens=8)[0]
    assert_greedy_equivalent(hf, prompt, out)


@pytest.mark.parametrize("parallel", [True, False])
def test_gptneox_import(tmp_path, parallel):
    cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.25,
        use_parallel_residual=parallel, max_position_embeddings=128,
        attn_implementation="eager")
    _logits_parity(transformers.GPTNeoXForCausalLM(cfg), tmp_path)


def test_bert_import(tmp_path):
    cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=128, attn_implementation="eager")
    hf = transformers.BertForMaskedLM(cfg)
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    import jax.numpy as jnp
    hf.eval()
    hf.save_pretrained(tmp_path, safe_serialization=True)
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 128, (2, 10))
    mask = np.ones_like(ids); mask[1, 7:] = 0
    with torch.no_grad():
        ref = hf(torch.tensor(ids),
                 attention_mask=torch.tensor(mask)).logits.float().numpy()
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(ids, jnp.int32),
                                 attention_mask=jnp.asarray(mask, jnp.int32)))
    # padded query rows attend nothing real in HF (softmax over -inf row
    # yields uniform) — compare only valid positions
    np.testing.assert_allclose(ref[0], got[0], rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(ref[1, :7], got[1, :7], rtol=2e-3, atol=2e-3)


def test_phi3_import_and_generate(tmp_path):
    """Phi-3 = llama decoder with fused qkv/gate_up — split onto the llama
    tree; greedy decode must track HF."""
    import jax.numpy as jnp
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
        attn_implementation="eager")
    hf = transformers.Phi3ForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = [3, 17, 9, 44]
    out = eng.generate(np.asarray([prompt]), max_new_tokens=8)[0]
    assert_greedy_equivalent(hf, prompt, out)


def test_qwen2_moe_import(tmp_path):
    """Qwen2-MoE: shared expert + routed experts + qkv bias, with
    norm_topk_prob=False (raw softmax top-k weights)."""
    cfg = transformers.Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=64, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        max_position_embeddings=128, intermediate_size=64,
        attn_implementation="eager")
    # capacity off for the parity run (mixtral test does the same): HF
    # never drops tokens, so a chance over-capacity expert would zero a
    # routed output only on our side
    from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig
    zoo_cfg = Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=64, norm_topk_prob=False,
        capacity_factor=100.0, max_position_embeddings=128, remat=False)
    # the HF weights from a seed of their own: drawn from whatever state the
    # worker's torch generator is in, one draw in some tens has a gate near
    # enough a tie to move more than 1% of the logits (PR 45: the second of
    # two whole runs of the suite)
    torch.manual_seed(0)
    _logits_parity(transformers.Qwen2MoeForCausalLM(cfg), tmp_path,
                   tie_tolerant=True, config=zoo_cfg)


def test_gptj_import_and_generate(tmp_path):
    """GPT-J: parallel residual off ONE LayerNorm, interleaved partial
    rotary, biased MLP/lm_head (reference containers/gptj.py)."""
    cfg = transformers.GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        rotary_dim=8, attn_implementation="eager")
    hf = transformers.GPTJForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = list(np.random.default_rng(1).integers(0, 128, 6))
    out = eng.generate(np.asarray([prompt]), max_new_tokens=4)
    assert_greedy_equivalent(hf, prompt, out[0])


def test_gptneo_import_and_generate(tmp_path):
    """GPT-Neo: alternating global/local(256) attention, UNSCALED logits,
    learned positions (reference containers/gptneo.py). window_size=8 at
    sequence 10 makes the local mask bite — parity fails if the band or
    the missing 1/sqrt(d) is wrong."""
    cfg = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position_embeddings=128,
        attention_types=[[["global", "local"], 1]], window_size=8,
        attn_implementation="eager")
    hf = transformers.GPTNeoForCausalLM(cfg)
    model, params = _logits_parity(hf, tmp_path)
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu
    groups.reset_topology()
    eng = deepspeed_tpu.init_inference((model, params), dtype="fp32")
    prompt = list(np.random.default_rng(2).integers(0, 128, 12))
    out = eng.generate(np.asarray([prompt]), max_new_tokens=4)
    assert_greedy_equivalent(hf, prompt, out[0])


def test_internlm_import(tmp_path):
    """InternLM-v1 = llama with bias on all four attention projections.
    Golden: HF llama with attention_bias=True saved, then the config
    rewritten to model_type=internlm/bias=true (HF internlm is
    trust_remote_code; the tensors and schema are identical)."""
    import json as _json
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        attention_bias=True, attn_implementation="eager")
    hf = transformers.LlamaForCausalLM(cfg)
    hf.eval()
    hf.save_pretrained(tmp_path, safe_serialization=True)
    cfg_path = tmp_path / "config.json"
    raw = _json.loads(cfg_path.read_text())
    raw["model_type"] = "internlm"
    raw["bias"] = True
    cfg_path.write_text(_json.dumps(raw))
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert "bias" in params["layers"]["self_attn"]["o_proj"]
    ids = np.random.default_rng(3).integers(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.float().numpy()
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(ids, jnp.int32)))
    np.testing.assert_allclose(ref, got, rtol=2e-3, atol=2e-3)


def test_llama_attention_bias_import(tmp_path):
    """Plain llama checkpoints with attention_bias=True import too."""
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        attention_bias=True, attn_implementation="eager")
    _logits_parity(transformers.LlamaForCausalLM(cfg), tmp_path)


def test_distilbert_import(tmp_path):
    """DistilBERT rides the BERT encoder (type_vocab_size=0) with the
    q/k/v/out_lin → query/key/value/output renaming (reference
    containers/distil_bert.py)."""
    cfg = transformers.DistilBertConfig(
        vocab_size=128, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        max_position_embeddings=128, attn_implementation="eager")
    hf = transformers.DistilBertForMaskedLM(cfg)
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    hf.eval()
    hf.save_pretrained(tmp_path, safe_serialization=True)
    model, params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    ids = np.random.default_rng(4).integers(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.float().numpy()
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(ids, jnp.int32)))
    np.testing.assert_allclose(ref, got, rtol=2e-3, atol=2e-3)


def test_untied_lm_head_rejected(tmp_path):
    """A falcon/bloom fine-tune with an UNTIED lm_head must fail at import
    (the zoo models tie the head to word_embeddings)."""
    cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        bias=False, new_decoder_architecture=False, alibi=False,
        attn_implementation="eager")
    hf = transformers.FalconForCausalLM(cfg).eval()
    hf.config.tie_word_embeddings = False
    with torch.no_grad():  # untie: perturb the head away from the embedding
        hf.lm_head.weight = torch.nn.Parameter(
            hf.transformer.word_embeddings.weight.clone() + 1.0)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    from deepspeed_tpu.module_inject import load_hf_checkpoint
    with pytest.raises(NotImplementedError, match="UNTIED lm_head"):
        load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)


def test_wrong_hidden_act_rejected():
    """A checkpoint whose activation differs from the family's hardcoded one
    must fail at config import, not drift silently."""
    from deepspeed_tpu.module_inject.load_checkpoint import from_hf_config
    with pytest.raises(NotImplementedError, match="hidden_act"):
        # falcon's HF config stores the activation under 'activation'
        from_hf_config({"model_type": "falcon", "vocab_size": 128,
                        "hidden_size": 64, "num_hidden_layers": 2,
                        "num_attention_heads": 4, "activation": "relu"})
    with pytest.raises(NotImplementedError, match="hidden_act"):
        from_hf_config({"model_type": "llama", "vocab_size": 128,
                        "hidden_size": 64, "intermediate_size": 128,
                        "num_hidden_layers": 2, "num_attention_heads": 4,
                        "hidden_act": "gelu"})
    with pytest.raises(NotImplementedError, match="hidden_act"):
        from_hf_config({"model_type": "gpt2", "vocab_size": 128,
                        "n_embd": 64, "n_layer": 2, "n_head": 4,
                        "activation_function": "relu"})
    # the defaults still import
    from_hf_config({"model_type": "llama", "vocab_size": 128,
                    "hidden_size": 64, "intermediate_size": 128,
                    "num_hidden_layers": 2, "num_attention_heads": 4,
                    "hidden_act": "silu"})
