from .config import ModelConfig
from .transformer import (layer_plan, init_params, forward, loss_fn,
                          init_cache, decode_step, prefill, param_count)
