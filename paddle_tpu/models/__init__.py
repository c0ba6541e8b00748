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


_LAZY = {"Dots3NoteConfig", "Dots3NoteForCausalLM", "Dots3NoteModel",
         "dots3_note_tiny"}


def __getattr__(name):
    # models/dots3_note is imported when asked for, not with the package
    if name in _LAZY:
        from . import dots3_note
        return getattr(dots3_note, name)
    raise AttributeError(name)
