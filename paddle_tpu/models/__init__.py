"""Model zoo (flagship training models; vision models live in
paddle_tpu.vision.models)."""
from .gpt import (GPTConfig, GPTModel, GPTForPretraining,
                  GPTPretrainingCriterion, gpt_config, PRESETS)
from .bert import (BertConfig, BertModel, BertForPretraining,
                   BertForQuestionAnswering,
                   BertForSequenceClassification,
                   BertPretrainingCriterion, bert_config, BERT_PRESETS)
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,
                    LlamaPretrainingCriterion, llama_config,
                    llama_pipeline_step, LLAMA_PRESETS)
from .ernie_moe import (ErnieMoEConfig, ErnieMoEModel,
                        ErnieMoEForPretraining, ernie_moe_config,
                        ERNIE_MOE_PRESETS)
from .mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from .solar_open2 import SolarOpen2Config, SolarOpen2ForCausalLM
from .glm5 import Glm5Config, Glm5ForCausalLM
from .t5 import T5Config, T5ForConditionalGeneration
from .bart import BartConfig, BartForConditionalGeneration
from .convert import (bert_from_hf, llama_from_hf, gpt2_from_hf,
                      mistral_from_hf, qwen2_from_hf, gemma_from_hf,
                      t5_from_hf, bart_from_hf)
