"""paddle_tpu.models — flagship model families.

The reference keeps GPT/BERT in PaddleNLP and exercises them through fleet
hybrid-parallel tests (python/paddle/fluid/tests/unittests/hybrid_parallel_*);
BASELINE.md configs 3/4 name BERT-base and GPT-1.3B. These are the TPU-native
flagships: built from paddle_tpu.nn + fleet parallel layers, with scan-over-
layers pipeline mode and hybrid dp/tp/pp/sp sharding specs.
"""
from .gpt import (  # noqa: F401
    GPTConfig, GPTForCausalLM, GPTModel, GPTPretrainingCriterion, gpt_presets,
    gpt_1f1b_grad_fn, gpt_1f1b_train_step,
)
from .mla_moe import (  # noqa: F401
    MlaMoeConfig, MlaMoeForCausalLM, MlaMoeModel,
)
from .gdn_moe import (  # noqa: F401
    GdnMoeConfig, GdnMoeForCausalLM, GdnMoeModel,
)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel,
)
from .bert import (  # noqa: F401
    BertConfig, BertForPretraining, BertModel, BertPretrainingCriterion,
    bert_presets,
)
from .wide_deep import (  # noqa: F401
    WideDeep, wide_deep_loss, ctr_batches, zipf_ids,
)
