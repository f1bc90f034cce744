from . import attention, cnn, common, moe, ssm, transformer, vit, xlstm
from .transformer import (cache_shapes, decode_step, forward, init_cache,
                          init_params, loss_fn, param_shapes, prefill)

__all__ = [
    "attention", "cache_shapes", "cnn", "common", "decode_step", "forward",
    "init_cache", "init_params", "loss_fn", "moe", "param_shapes", "prefill",
    "ssm", "transformer", "vit", "xlstm",
]
