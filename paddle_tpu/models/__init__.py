"""Flagship model implementations (GPT pretraining, BERT, OCR det+rec)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForPretraining, GPTBlock, GPTAttention, GPTMLP,
    gpt_tiny_config,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification, BertForPretraining,
    ErnieConfig, ErnieModel, ErnieForSequenceClassification,
    ErnieForPretraining, ernie_knowledge_mask,
)
from .ocr import (  # noqa: F401
    CRNN, DBNet, db_loss, ctc_greedy_decode,
)
from .deepseek_v2 import (  # noqa: F401
    DeepseekV2Config, DeepseekV2ForCausalLM,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM,
)
from .exaone_moe import (  # noqa: F401
    ExaoneMoeConfig, ExaoneMoeForCausalLM,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig, Qwen3NextForCausalLM,
)
