"""Pallas TPU kernels (SURVEY §7.2): the fused ops XLA won't fuse well.

Replaces the reference's CUDA fusion zoo (phi/kernels/fusion/gpu/*,
fused_attention_op.cu, fused_rms_norm, cutlass attention) with TPU-native
Pallas kernels. Import is lazy/defensive: on CPU test meshes the jnp
fallbacks in nn.functional are used instead.
"""
from . import flash_attention  # noqa: F401
from . import fused_norm_residual  # noqa: F401
from . import gated_delta_rule  # noqa: F401
from . import grouped_matmul  # noqa: F401
from . import rms_norm  # noqa: F401
from . import rope  # noqa: F401
from . import swiglu  # noqa: F401
