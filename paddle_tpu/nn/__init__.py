"""paddle_tpu.nn (ref: python/paddle/nn/)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer.layers import Layer, ParamAttr  # noqa: F401
from .layer.common import *       # noqa: F401,F403
from .layer.extras import *       # noqa: F401,F403
from .layer.conv import *         # noqa: F401,F403
from .layer.norm import *         # noqa: F401,F403
from .layer.activation import *   # noqa: F401,F403
from .layer.pooling import *      # noqa: F401,F403
from .layer.loss import *         # noqa: F401,F403
from .layer.container import *    # noqa: F401,F403
from .layer.transformer import *  # noqa: F401,F403
from .layer.moe import *          # noqa: F401,F403
from .layer.rnn import *          # noqa: F401,F403
from .decode import (BeamSearchDecoder, Decoder,  # noqa: F401
                     dynamic_decode)
from .clip import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: F401
                   ClipGradByValue)


def clip_grad_norm_(parameters, max_norm, norm_type=2.0,
                    error_if_nonfinite=False):
    """ref: python/paddle/nn/utils/clip_grad_norm_.py."""
    import jax.numpy as jnp
    from ..tensor import Tensor
    params = [p for p in (parameters if isinstance(parameters, (list, tuple))
                          else [parameters]) if p.grad is not None]
    if not params:
        return Tensor(jnp.zeros(()))
    if norm_type == float("inf"):
        total = jnp.max(jnp.stack([jnp.max(jnp.abs(p.grad.data)) for p in params]))
    else:
        total = jnp.sum(jnp.stack(
            [jnp.sum(jnp.abs(p.grad.data.astype(jnp.float32)) ** norm_type)
             for p in params])) ** (1.0 / norm_type)
    if error_if_nonfinite:
        import numpy as _np
        # required sync: raising a python exception on a non-finite norm
        # is the documented contract of error_if_nonfinite=True, and the
        # verdict must be on host to raise (opt-in, off the default path)
        if not _np.isfinite(float(total)):  # graft-lint: disable=host-sync
            raise RuntimeError(
                "The total norm of gradients is non-finite, so it cannot "
                "be clipped (set error_if_nonfinite=False to skip)")
    clip_coef = jnp.minimum(max_norm / (total + 1e-6), 1.0)
    for p in params:
        p.grad.data = (p.grad.data.astype(jnp.float32) * clip_coef).astype(
            p.grad.dtype)
    return Tensor(total)


class utils:
    clip_grad_norm_ = staticmethod(clip_grad_norm_)
