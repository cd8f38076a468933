"""Model zoo: LLM families built on paddle_tpu layers."""
from .llama import (LlamaConfig, LlamaMLP, LlamaAttention, LlamaDecoderLayer,
                    LlamaModel, LlamaForCausalLM, LlamaPretrainingCriterion)
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt_pipeline_layers
from .bert import (BertConfig, BertModel, BertForMaskedLM,
                   BertForSequenceClassification)
from .mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from .dots3_note import Dots3NoteConfig, Dots3NoteForCausalLM
from .keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM
from .cohere2_moe import Cohere2MoeConfig, Cohere2MoeForCausalLM
