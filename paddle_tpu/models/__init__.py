"""Language-model zoo — the flagship training models of the framework.

The reference keeps its LLMs in the PaddleNLP ecosystem built on the
fleet/meta_parallel primitives (upstream: python/paddle/distributed/
fleet/layers/mpu/mp_layers.py provides the TP layers those models use);
this framework ships the acceptance-config model families in-tree:

* :mod:`.llama`  — Llama-2 (RMSNorm / RoPE / GQA / SwiGLU), TP/SP-aware
* :mod:`.gpt`    — GPT-3 (pre-LN, learned positions, gelu), DP/sharding
* :mod:`.xing4`  — Xing4.0 (MLA, drop-free sigmoid-routed experts with a
  shared expert, mHC residual streams, MTP), served from latent pages
* :mod:`.evabyte` — EvaByte (byte-level; EVA window-and-summary attention,
  unit-offset norms, 8 next-byte heads), served from window-and-summary
  pages by the Llama adapter
* :mod:`.sdar`   — SDAR-MoE (Qwen3-MoE's layer: per-head q/k norms,
  softmax-routed drop-free experts; generation by diffusion over blocks),
  served from K/V pages by the Llama adapter, a block of tokens a row
* :mod:`.bert`   — BERT (bidirectional post-norm encoder, MLM +
  sequence-classification heads), non-causal flash path
"""
from . import llama
from . import gpt
from . import bert
from . import t5
from .t5 import (
    T5Config,
    T5ForConditionalGeneration,
    t5_base,
    t5_small,
    t5_tiny,
)
from .bert import (
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
    bert_base,
    bert_large,
    bert_tiny,
)
from .llama import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    LlamaPretrainingCriterion,
    llama2_7b,
    llama_headline,
    llama2_13b,
    llama3_8b,
    llama3_70b,
    llama_tiny,
    llama_pipeline_model,
    mistral_7b,
    mixtral_8x7b,
    mixtral_tiny,
    qwen2_0_5b,
    qwen2_7b,
)
from .gpt import (
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    ernie_moe_base,
    gpt3_1_3b,
    gpt3_6_7b,
    gpt_moe_tiny,
    gpt_pipeline_model,
    gpt_tiny,
)
from .xing4 import (  # noqa: E402
    Xing4Config,
    Xing4ForCausalLM,
    Xing4Model,
    xing4_29b_a4b,
    xing4_tiny,
)
from .sdar import (  # noqa: E402
    SDARMoeConfig,
    SDARMoeForCausalLM,
    SDARMoeModel,
    sdar_30b_a3b,
    sdar_tiny,
)
from .evabyte import (  # noqa: E402
    EvaByteConfig,
    EvaByteForCausalLM,
    EvaByteModel,
    evabyte_6_5b,
    evabyte_tiny,
)
from .generation import generate, speculative_generate  # noqa: E402
