from pbs_tpu.ops.attention import flash_attention
from pbs_tpu.ops.grouped_matmul import grouped_matmul
from pbs_tpu.ops.kda_step import kda_state_step
from pbs_tpu.ops.kv_attend import kv_attend
from pbs_tpu.ops.mamba_scan import mamba_prompt_scan
from pbs_tpu.ops.matmul import (
    MatmulStats,
    instrumented_matmul,
    scale_stats,
)
from pbs_tpu.ops.mla_attend import mla_attend

__all__ = [
    "MatmulStats",
    "flash_attention",
    "grouped_matmul",
    "instrumented_matmul",
    "kda_state_step",
    "kv_attend",
    "mamba_prompt_scan",
    "mla_attend",
    "scale_stats",
]
