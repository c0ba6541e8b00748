"""The step programs of the benchmark's five models at tiny sizes, held to
the parent commit's text: the StableHLO of the CPU route and the jaxpr of
the TPU route (`flash_attention._on_tpu` patched: the splash wrapper with
its window and value width), character for character. A change to shared
model code (`models/pieces.py`, `models/dots3_note.py`, `nn/layer/moe.py`,
`nn/functional/loss.py`) that moves any of the five shows here; pin again
only after reading the two texts side by side (`texts_of` makes them).
A model is built once for both routes, a `TrainStep` once a route (jax
keeps a traced function's jaxpr, so a second route needs a second step)."""
import hashlib
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _compiled  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.optimizer as popt  # noqa: E402
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402

ROUTES = ("cpu_text", "tpu_jaxpr")

PARENT = {       # sha256 of the normalised text at commit 7e1cdc7 (PR 46),
    # read under this suite's conftest (8 host devices). The two
    # `llama_gqa.*` are PR 32's still; `solar.*` and `granite.*` are PR 46's
    # (PR 37's delta-rule block, PR 45's row moves, PR 46's head + loss);
    # `dots3.*` and `glm.*` were first pinned by PR 47, before its edit. All
    # ten held through PR 48, which moved the half-layers these models
    # share (`LatentAttention`, the expert half, `SwiGLUHalf`) onto
    # `pieces.Residual`: the plain form's add is where it was, to the
    # character. PR 50 pinned `llama_gqa.tpu_jaxpr`, `solar.tpu_jaxpr` and
    # `glm.tpu_jaxpr` again: their dense-causal splash calls take the ONE
    # backward kernel. Read side by side, primitive by primitive outside
    # the kernels' bodies: a call site loses `splash_mqa_dq_no_residuals`
    # with the broadcasts and squeezes that fed it, its dkv call gains the
    # output of dq's copies, and one `reduce_sum` over them is new; every
    # other count is the parent's. Every `*.cpu_text`, `granite.tpu_jaxpr`
    # (its tiny heads take no kernel) and `dots3.tpu_jaxpr` (its splash
    # calls are under a window: two kernels) hold unedited
    "llama_gqa.cpu_text": "b4b176201bc8d8fbafa942c340cb4a468ec2b616380afc060286c729b452eeeb",
    "solar.cpu_text": "828a6756db725ea97a7568847957159b50837da7a6c1d0b4ac2844606f3d0083",
    "granite.cpu_text": "557ddf2fb05388c761d8d5d4256b73f3c7542a3d10d555e0f35270360e53f8e0",
    "llama_gqa.tpu_jaxpr": "a8e05eb9257573901e89b9c02d9bcbc53ade549911a6a994227bd29d0d8288d6",
    "solar.tpu_jaxpr": "61d96f8e35b01b8cebfa0d74612cd35759c478145b65f41a0d5791d5d860297e",
    "granite.tpu_jaxpr": "3643417b69b7f9a24fa25e40435d9c7bb2be836732cabf44334cc7170a6cd946",
    "dots3.cpu_text": "5ebd5b1382dd6aafaa88b05739bb2c226653207d889be5c87b7a1ab9aa52fba0",
    "dots3.tpu_jaxpr": "43fd7279ca8978e0dc2beb55777e0a3a6a8bbeb60ae5603e483d798066d3ce36",
    "glm.cpu_text": "7b3cb6fa5de3c4920a65f7ea239298fec8c530a7a04407ba0e9de39afa64c35e",
    "glm.tpu_jaxpr": "b655fdcb5f4032112e6ca6a8a34127b6e97bbe2c23f4b8fe170b2553e616ab9a",
}


def _model(name):
    """Widths the kernels take on the TPU route (keys of 64), the same
    model on both; built abstractly: the text reads shapes alone."""
    if name == "llama_gqa":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        make = lambda: LlamaForCausalLM(LlamaConfig(
            vocab_size=96, hidden_size=256, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            dtype="float32"))
    elif name == "solar":
        from paddle_tpu.models.solar_open2 import (SolarOpen2ForCausalLM,
                                                   solar_open2_tiny)
        make = lambda: SolarOpen2ForCausalLM(solar_open2_tiny(head_dim=64))
    elif name == "granite":
        from paddle_tpu.models.granite_hybrid import (
            GraniteHybridForCausalLM, granite_hybrid_tiny)
        make = lambda: GraniteHybridForCausalLM(granite_hybrid_tiny())
    elif name == "dots3":
        from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                                  dots3_note_tiny)
        make = lambda: Dots3NoteForCausalLM(dots3_note_tiny(
            index_n_heads=8, swa_qk_nope_head_dim=60, swa_v_head_dim=64,
            v_head_dim=64, qk_nope_head_dim=60))
    else:
        from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteForCausalLM,
                                                     glm4_moe_lite_tiny)
        # values wider than the keys: the splash route, as at the cell's 256
        make = lambda: Glm4MoeLiteForCausalLM(glm4_moe_lite_tiny(
            qk_nope_head_dim=60, v_head_dim=128))
    return _compiled.shapes_only(make)


def normalised(text):
    """Memory addresses in a repr and the step's executable tag (which
    counts the steps the process has built) out."""
    return re.sub(r"0x[0-9a-f]+|train_step_\d+", "0x", text)


def texts_of(name):
    """{route: the normalised text} of one model's step."""
    model = _model(name)
    x = paddle.to_tensor(np.zeros((1, 128), np.int32))
    out = {}
    was = fa._on_tpu
    try:
        for route in ROUTES:
            fa._on_tpu = lambda: route == "tpu_jaxpr"
            opt = popt.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
            step = paddle.jit.TrainStep(model, opt,
                                        lambda i, l: model.loss(i, l))
            if route == "tpu_jaxpr":
                step._build()
                text = str(step._compiled.trace(
                    *step._call_args((x, x))).jaxpr)
            else:
                text = step.lower(x, x).as_text()
            out[route] = normalised(text)
    finally:
        fa._on_tpu = was
    return out


@pytest.fixture(scope="module")
def texts():
    made = {}

    def of(name):
        if name not in made:
            made[name] = texts_of(name)
        return made[name]

    return of


@pytest.mark.parametrize("key", sorted(PARENT))
def test_existing_models_lower_to_the_parents_program(key, texts):
    """The Yi cells' model (LLaMA, GQA), Solar-Open2, Granite, dots3-note
    and GLM-4.7-Flash through `TrainStep`."""
    name, route = key.split(".")
    got = hashlib.sha256(texts(name)[route].encode()).hexdigest()
    assert got == PARENT[key]
