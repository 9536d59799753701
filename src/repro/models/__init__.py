from .config import ModelConfig, reduced  # noqa: F401
from .model import (  # noqa: F401
    decode_step,
    forward,
    init_cache,
    init_params,
    last_position_logits,
    loss_fn,
    prefill,
)
from .partition import param_logical_axes, param_shardings  # noqa: F401
