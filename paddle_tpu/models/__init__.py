"""``paddle_tpu.models`` — flagship model families.

The reference keeps its LLM recipes out-of-tree (PaddleNLP), but the
BASELINE north star is Llama-3-8B pretraining MFU, so the decoder family
lives in-tree here, built on the incubate fused ops + Pallas GQA flash
attention.
"""

from .llama import (  # noqa: F401
    LlamaConfig, LlamaMLP, LlamaMoEMLP, LlamaAttention, LlamaDecoderLayer, LlamaModel,
    LlamaForCausalLM, shard_llama, llama3_8b_config, tiny_llama_config,
)
from .mla_moe import (  # noqa: F401
    MlaMoeConfig, MlaAttention, MlaMoeMLP, MlaMoeDecoderLayer, MlaMoeModel,
    MlaMoeForCausalLM, expert_stats, tiny_mla_moe_config,
)
from .sambay import (  # noqa: F401
    SambaYConfig, SambaYDecoderLayer, SambaYModel, SambaYForCausalLM,
    tiny_sambay_config,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig, KdaAttention, KimiLinearDecoderLayer, KimiLinearModel,
    KimiLinearForCausalLM, tiny_kimi_linear_config,
)
from .llama_pipe import LlamaForCausalLMPipe  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification,
    BertForTokenClassification, ErnieModel,
    ErnieForSequenceClassification, ernie_base_config, tiny_bert_config,
)

__all__ = [
    "LlamaConfig", "LlamaMLP", "LlamaMoEMLP", "LlamaAttention", "LlamaDecoderLayer",
    "LlamaModel", "LlamaForCausalLM", "shard_llama", "llama3_8b_config",
    "tiny_llama_config", "LlamaForCausalLMPipe",
    "MlaMoeConfig", "MlaAttention", "MlaMoeMLP", "expert_stats",
    "MlaMoeDecoderLayer", "MlaMoeModel", "MlaMoeForCausalLM",
    "tiny_mla_moe_config",
    "SambaYConfig", "SambaYDecoderLayer", "SambaYModel",
    "SambaYForCausalLM", "tiny_sambay_config",
    "KimiLinearConfig", "KdaAttention", "KimiLinearDecoderLayer",
    "KimiLinearModel", "KimiLinearForCausalLM", "tiny_kimi_linear_config",
    "BertConfig", "BertModel", "BertForSequenceClassification",
    "BertForTokenClassification", "ErnieModel",
    "ErnieForSequenceClassification", "ernie_base_config",
    "tiny_bert_config",
]
