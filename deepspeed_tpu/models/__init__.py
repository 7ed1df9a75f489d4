from deepspeed_tpu.models.gpt2 import (
    GPT2Config, GPT2_SMALL, GPT2_MEDIUM, GPT2_LARGE, GPT2_XL,
    gpt2_forward, gpt2_loss_fn, gpt2_param_specs, gpt2_pipeline_spec,
    gpt2_sp_loss_fn, init_gpt2_params, count_params, write_kv_cache)
from deepspeed_tpu.ops.attention.page_pool import causal_cache_mask
from deepspeed_tpu.models.bert import (
    BertConfig, BERT_BASE, BERT_LARGE, bert_encoder, bert_mlm_loss_fn,
    bert_mlm_sp_loss_fn, bert_param_specs, init_bert_params)
from deepspeed_tpu.models.llama import (
    LlamaConfig, init_llama_params, llama_forward, llama_generate,
    llama_loss_fn, llama_param_specs)
from deepspeed_tpu.models.smallthinker import (
    SmallThinkerConfig, init_smallthinker_params, smallthinker_logits,
    smallthinker_loss_fn)
from deepspeed_tpu.models.granite_hybrid import (
    GraniteHybridConfig, granite_hybrid_forward, init_granite_hybrid_params)
from deepspeed_tpu.models.solar_open2 import (
    SolarOpen2Config, init_solar_open2_params, solar_open2_forward)
from deepspeed_tpu.models.axk1 import (
    AXK1Config, axk1_forward, init_axk1_params)
from deepspeed_tpu.models.lfm2 import (
    LFM2Config, init_lfm2_params, lfm2_forward)
from deepspeed_tpu.models.keye_vl2 import (
    KeyeVL2Config, init_keye_vl2_params, keye_vl2_forward)
