from .base import Model, ModelConfig, get_model_class, register_model  # noqa: F401
from .bert import Bert, bert_config  # noqa: F401
from .bloom import Bloom, bloom_config  # noqa: F401
from .deepseek_v3 import DeepseekV3, deepseek_v3_config  # noqa: F401
from .falcon import Falcon, falcon_config  # noqa: F401
from .gpt2 import GPT2, gpt2_config  # noqa: F401
from .gptj import GPTJ, gptj_config  # noqa: F401
from .gptneox import GPTNeoX, gptneox_config  # noqa: F401
from .granite_hybrid import GraniteHybrid, granite_hybrid_config  # noqa: F401
from .internlm import InternLM, internlm_config  # noqa: F401
from .kimi_linear import KimiLinear, kimi_linear_config  # noqa: F401
from .laguna import Laguna, laguna_config  # noqa: F401
from .lfm2_moe import Lfm2Moe, lfm2_moe_config  # noqa: F401
from .llama import Llama, llama_config  # noqa: F401
from .mellum import Mellum, mellum_config  # noqa: F401
from .mistral import Mistral, mistral_config  # noqa: F401
from .mixtral import Mixtral, mixtral_config  # noqa: F401
from .nemotron_h import NemotronH, nemotron_h_config  # noqa: F401
from .opt import OPT, opt_config  # noqa: F401
from .ouro import Ouro, ouro_config  # noqa: F401
from .phi import Phi, Phi3, phi3_config, phi_config  # noqa: F401
from .qwen import (Qwen, Qwen2, Qwen2MoE, qwen2_config,  # noqa: F401
                   qwen2_moe_config, qwen_config)
from .qwen3_next import Qwen3Next, qwen3_next_config  # noqa: F401
from .transformer import DecoderLM  # noqa: F401
from .xing4 import Xing4, xing4_config  # noqa: F401


def from_pretrained(model_path: str, **config_overrides):
    """(model, params) from a local HF checkpoint directory — see
    checkpoint/huggingface.py (reference: inference/v2/checkpoint/
    huggingface_engine.py)."""
    from ..checkpoint.huggingface import from_pretrained as _fp
    return _fp(model_path, **config_overrides)
