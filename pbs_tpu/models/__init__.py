import importlib

from pbs_tpu.models.flagship import flagship_config
from pbs_tpu.models.generate import (
    forward_with_cache,
    init_cache,
    make_generate,
    make_serve_step,
    prefill,
)
from pbs_tpu.models.microstep import make_micro_train_step
from pbs_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    make_moe_generate,
    make_moe_train_step,
    moe_forward,
    moe_forward_with_cache,
    moe_loss,
)
from pbs_tpu.models.quant import quantize_weights, quantized_nbytes
from pbs_tpu.models.speculative import (
    make_speculative_generate,
    make_speculative_serve_step,
)
from pbs_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    make_eval_step,
    make_train_step,
    next_token_loss,
)

__all__ = [
    "Completion",
    "ContinuousBatcher",
    "SpeculativeBatcher",
    "MoEConfig",
    "TransformerConfig",
    "flagship_config",
    "forward",
    "make_continuous_serve_step",
    "forward_with_cache",
    "init_cache",
    "init_moe_params",
    "init_params",
    "make_eval_step",
    "make_generate",
    "make_micro_train_step",
    "make_moe_generate",
    "make_moe_train_step",
    "moe_forward_with_cache",
    "make_serve_step",
    "make_speculative_generate",
    "make_speculative_serve_step",
    "make_train_step",
    "moe_forward",
    "moe_loss",
    "next_token_loss",
    "prefill",
    "quantize_weights",
    "quantized_nbytes",
]

#: The slot engines, each imported from the module that defines it when
#: first asked for: what a configuration's layer stack is
#: (``models/slot_programs.py``) and every model module beside it load
#: without the engine, as they know nothing of it.
_ENGINES = {
    "Completion": "pbs_tpu.models.serving",
    "ContinuousBatcher": "pbs_tpu.models.serving",
    "make_continuous_serve_step": "pbs_tpu.models.serving",
    "SpeculativeBatcher": "pbs_tpu.models.spec_serving",
}


def __getattr__(name: str):
    if name in _ENGINES:
        return getattr(importlib.import_module(_ENGINES[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
