from deepspeed_tpu.models.bert import (
    BertConfig, BertForMaskedLM, bert_config, bert_loss_fn, init_bert)
from deepspeed_tpu.models.bloom import (
    BloomConfig, BloomForCausalLM, bloom_config, bloom_loss_fn, init_bloom)
from deepspeed_tpu.models.falcon import (
    FalconConfig, FalconForCausalLM, falcon_config, falcon_loss_fn, init_falcon)
from deepspeed_tpu.models.gpt2 import (
    GPT2Config, GPT2LMHeadModel, gpt2_config, gpt2_loss_fn, init_gpt2)
from deepspeed_tpu.models.gptj import (
    GPTJConfig, GPTJForCausalLM, gptj_config, gptj_loss_fn, init_gptj)
from deepspeed_tpu.models.gptneo import (
    GPTNeoConfig, GPTNeoForCausalLM, gptneo_config, gptneo_loss_fn,
    init_gptneo)
from deepspeed_tpu.models.gptneox import (
    GPTNeoXConfig, GPTNeoXForCausalLM, gptneox_config, gptneox_loss_fn,
    init_gptneox)
from deepspeed_tpu.models.phi import (
    PhiConfig, PhiForCausalLM, init_phi, phi_config, phi_loss_fn)
from deepspeed_tpu.models.llama import (
    LlamaConfig, LlamaForCausalLM, init_params_and_specs, llama_config,
    llama_loss_fn, materialize_params)
from deepspeed_tpu.models.mistral import (
    MistralConfig, MistralForCausalLM, mistral_config)
from deepspeed_tpu.models.qwen2_moe import (
    Qwen2MoeConfig, Qwen2MoeForCausalLM, init_qwen2_moe, qwen2_moe_config,
    qwen2_moe_loss_fn)
from deepspeed_tpu.models.qwen2 import (
    Qwen2Config, Qwen2ForCausalLM, qwen2_config)
from deepspeed_tpu.models.nemotron_h import (
    NemotronHConfig, NemotronHForCausalLM, nemotron_h_loss_fn)
from deepspeed_tpu.models.phi4flash import (
    Phi4FlashConfig, Phi4FlashForCausalLM, phi4flash_loss_fn)
from deepspeed_tpu.models.ling_linear import (
    LingLinearConfig, LingLinearForCausalLM, ling_linear_loss_fn)
from deepspeed_tpu.models.keye_sparse import (
    KeyeSparseConfig, KeyeSparseForCausalLM, keye_sparse_loss_fn)
from deepspeed_tpu.models.deepseek_sparse import (
    DeepseekSparseConfig, DeepseekSparseForCausalLM, deepseek_sparse_loss_fn)
from deepspeed_tpu.models.openpangu import (
    OpenPanguConfig, OpenPanguForCausalLM, openpangu_loss_fn)
from deepspeed_tpu.models.afmoe import (
    AfmoeConfig, AfmoeForCausalLM, afmoe_loss_fn)
from deepspeed_tpu.models.qwen3_next import (
    Qwen3NextConfig, Qwen3NextForCausalLM, qwen3_next_loss_fn)
