"""Model registry.

Parity: reference `hf_models/register_hf.py:24-63` registers 5 custom families with HF Auto
classes; here registration is a plain dict keyed by `model_type` (the same strings, so configs
and converted checkpoints interop). `is_custom_model` / TP-compat predicates carry over; under
GSPMD every registered model is tensor-parallel capable (sharding is declarative), so
`is_tensor_parallel_compatible_model` returns True for all registered types.
"""

from .config import (
    AfmoeConfig,
    CommonConfig,
    DenseMoEConfig,
    EncDecDolomiteConfig,
    GPTCrossLayerConfig,
    JoyAIFlashConfig,
    Lfm2MoeConfig,
    MoEConfig,
    NemotronHConfig,
    OuroConfig,
    RNNDolomiteConfig,
)
from .gpt_dolomite import CausalLMOutput, GPTDolomiteForCausalLM, GPTDolomiteModel
from .afmoe import AfmoeForCausalLM, AfmoeModel
from .dense_moe import DenseMoEForCausalLM, DenseMoEModel
from .enc_dec_dolomite import EncDecDolomiteForSeq2SeqLM
from .gpt_crosslayer import (
    GPTCrossLayerForCausalLM,
    GPTCrossLayerModel,
    convert_gpt_dolomite_to_gpt_crosslayer,
)
from .joyai_flash import JoyAIFlashForCausalLM, JoyAIFlashModel
from .lfm2_moe import Lfm2MoeForCausalLM, Lfm2MoeModel
from .moe_dolomite import MoEDolomiteForCausalLM, MoEDolomiteModel
from .nemotron_h import NemotronHForCausalLM, NemotronHModel
from .ouro import OuroForCausalLM, OuroModel
from .rnn_dolomite import RNNDolomiteForCausalLM, RNNDolomiteModel

_CONFIG_CLASSES: dict[str, type] = {
    "gpt_dolomite": CommonConfig,
    "moe_dolomite": MoEConfig,
    "gpt_crosslayer": GPTCrossLayerConfig,
    "dense_moe": DenseMoEConfig,
    "rnn_dolomite": RNNDolomiteConfig,
    "enc_dec_dolomite": EncDecDolomiteConfig,
    "nemotron_h": NemotronHConfig,
    "joyai_llm_flash": JoyAIFlashConfig,
    "lfm2_moe": Lfm2MoeConfig,
    "ouro": OuroConfig,
    "afmoe": AfmoeConfig,
}

_MODEL_CLASSES: dict[str, type] = {
    "gpt_dolomite": GPTDolomiteForCausalLM,
    "moe_dolomite": MoEDolomiteForCausalLM,
    "gpt_crosslayer": GPTCrossLayerForCausalLM,
    "dense_moe": DenseMoEForCausalLM,
    "rnn_dolomite": RNNDolomiteForCausalLM,
    "enc_dec_dolomite": EncDecDolomiteForSeq2SeqLM,
    "nemotron_h": NemotronHForCausalLM,
    "joyai_llm_flash": JoyAIFlashForCausalLM,
    "lfm2_moe": Lfm2MoeForCausalLM,
    "ouro": OuroForCausalLM,
    "afmoe": AfmoeForCausalLM,
}

# families trained/driven through the seq2seq (AutoModelForSeq2SeqLM) surface
_ENCODER_DECODER_TYPES = {"enc_dec_dolomite"}


def is_encoder_decoder_model(model_type: str) -> bool:
    return model_type in _ENCODER_DECODER_TYPES


def register_model(
    model_type: str, config_cls: type, model_cls: type, is_encoder_decoder: bool = False
) -> None:
    _CONFIG_CLASSES[model_type] = config_cls
    _MODEL_CLASSES[model_type] = model_cls
    if is_encoder_decoder:
        _ENCODER_DECODER_TYPES.add(model_type)


def get_config_class(model_type: str) -> type:
    if model_type not in _CONFIG_CLASSES:
        raise ValueError(f"unknown model_type '{model_type}'")
    return _CONFIG_CLASSES[model_type]


def get_model_class(model_type: str) -> type:
    if model_type not in _MODEL_CLASSES:
        raise ValueError(f"unknown model_type '{model_type}'")
    return _MODEL_CLASSES[model_type]


def is_custom_model(model_type: str) -> bool:
    return model_type in _MODEL_CLASSES


def is_tensor_parallel_compatible_model(model_type: str) -> bool:
    # all JAX models are TP-compatible: sharding is declarative (GSPMD), not a class swap
    return is_custom_model(model_type)


def config_from_dict(d: dict) -> CommonConfig:
    model_type = d.get("model_type", "gpt_dolomite")
    return get_config_class(model_type).from_dict(d)
