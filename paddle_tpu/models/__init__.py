"""Model zoo.

The reference ships vision models in ``python/paddle/vision/models`` and
leaves LLMs to PaddleNLP; this framework's flagship trainables live here so
the cells of ``BENCHMARK.json`` (adapters in ``perf/models/``) and the driver
entry hooks have a canonical model family to exercise.
"""
from . import gpt  # noqa: F401
from .gpt import GPTConfig, GPTModel, GPTForCausalLM  # noqa: F401
from . import bert  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining,
    BertForSequenceClassification)
from . import llama  # noqa: F401
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM  # noqa: F401
from . import generation  # noqa: F401
from .generation import generate  # noqa: F401
from . import lfm2  # noqa: F401
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM  # noqa: F401
from . import deepseek_v3  # noqa: F401
from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM  # noqa: F401
from . import kimi_linear  # noqa: F401
from .kimi_linear import KimiLinearConfig, KimiLinearForCausalLM  # noqa: F401
from . import mellum  # noqa: F401
from .mellum import MellumConfig, MellumForCausalLM  # noqa: F401
from . import laguna  # noqa: F401
from .laguna import LagunaConfig, LagunaForCausalLM  # noqa: F401
