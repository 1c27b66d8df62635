from .gpt import (GPT_CONFIGS, GPTConfig, GPTForPretraining, GPTModel,  # noqa: F401
                  gpt_preset, make_gpt_train_step)
from .bert import (BERT_CONFIGS, BertConfig, BertModel, bert_preset,  # noqa: F401
                   make_bert_train_step)
from .ernie_moe import (ErnieMoeConfig, ErnieMoeModel,  # noqa: F401
                        make_ernie_moe_train_step)
from .pangu_moe import PanguMoeConfig, PanguMoeModel  # noqa: F401
from .evabyte import EvaByteConfig, EvaByteModel  # noqa: F401
from .longcat_flash import LongcatFlashConfig, LongcatFlashModel  # noqa: F401
