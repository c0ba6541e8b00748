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
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, SolarOpen2ForCausalLM, SolarOpen2Model,
    solar_open2_tiny,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM, GraniteHybridModel,
    granite_hybrid_tiny,
)


_LAZY = {"Dots3NoteConfig": "dots3_note", "Dots3NoteForCausalLM": "dots3_note",
         "Dots3NoteModel": "dots3_note", "dots3_note_tiny": "dots3_note",
         "Glm4MoeLiteConfig": "glm4_moe_lite",
         "Glm4MoeLiteForCausalLM": "glm4_moe_lite",
         "Glm4MoeLiteModel": "glm4_moe_lite",
         "glm4_moe_lite_tiny": "glm4_moe_lite"}


def __getattr__(name):
    # models/dots3_note and models/glm4_moe_lite (built from its layers) are
    # imported when asked for, not with the package
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module("." + _LAZY[name], __name__),
                       name)
    raise AttributeError(name)
