"""Flagship model families (PaddleNLP/PaddleClas-parity models running on the
TPU-native framework)."""
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, bert_base, bert_large,
    synthetic_mlm_batch,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, gpt_small, gpt3_1p3b,
    build_pipeline_layer, synthetic_lm_batch,
)
from .ouro import (  # noqa: F401
    OuroConfig, OuroModel, OuroForCausalLM,
)
from .joyai import (  # noqa: F401
    JoyAIFlashConfig, JoyAIFlashModel, JoyAIFlashForCausalLM,
)
from .lfm2 import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeModel, Lfm2MoeForCausalLM,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig, Qwen3NextModel, Qwen3NextForCausalLM,
)
from .ctr import (  # noqa: F401
    WideAndDeep, synthetic_ctr_batches, build_ctr_scan_step,
    train_ctr_windows,
)
