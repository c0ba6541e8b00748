"""Flagship model zoo (BASELINE.md configs 1-5)."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_1b, llama_350m,
    llama_7b, llama_tiny,
)
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    bert_base, bert_large, bert_tiny,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieForPretraining, ErnieModel, build_ernie_pipeline,
    ernie_3_0_medium, ernie_base, ernie_tiny,
)
from .unet import (  # noqa: F401
    UNet2DConditionModel, UNetConfig, unet_sd15, unet_tiny,
)


# built from `models/pieces.py`; imported when asked for, not with the package
_LAZY = {
    "solar_open2": ("SolarOpen2Config", "SolarOpen2ForCausalLM",
                    "SolarOpen2Model", "solar_open2_tiny"),
    "granite_hybrid": ("GraniteHybridConfig", "GraniteHybridForCausalLM",
                       "GraniteHybridModel", "granite_hybrid_tiny"),
    "dots3_note": ("Dots3NoteConfig", "Dots3NoteForCausalLM",
                   "Dots3NoteModel", "dots3_note_tiny"),
    "glm4_moe_lite": ("Glm4MoeLiteConfig", "Glm4MoeLiteForCausalLM",
                      "Glm4MoeLiteModel", "glm4_moe_lite_tiny"),
    "xing4_0": ("Xing40Config", "Xing40ForCausalLM", "Xing40Model",
                "xing4_0_tiny"),
    "lfm2_moe": ("Lfm2MoeConfig", "Lfm2MoeForCausalLM", "Lfm2MoeModel",
                 "lfm2_moe_tiny"),
}


def __getattr__(name):
    for module, names in _LAZY.items():
        if name in names:
            import importlib
            return getattr(importlib.import_module("." + module, __name__),
                           name)
    raise AttributeError(name)
