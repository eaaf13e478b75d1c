"""TPU kernel library: pallas kernels for the hot ops plus XLA reference
implementations used on CPU and as numerics oracles in tests.

Every pallas kernel exported here must have an interpret-mode test
module under tests/ (numerics, on the CPU) and an AOT compile in
tests/test_tpu_compile.py (does the chip's compiler accept it) —
enforced by graftcheck's pallas-interpret-test and kernel-exports rules,
see docs/static-analysis.md.
"""

from ray_tpu.ops.attention import causal_attention, reference_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.fused_ce import fused_lm_ce
from ray_tpu.ops.pipeline import pipeline_apply, stack_stage_params
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.ops.ssm_scan import selective_scan
from ray_tpu.ops.vocab_ce import streaming_ce

__all__ = [
    "causal_attention",
    "flash_attention",
    "fused_lm_ce",
    "pipeline_apply",
    "reference_attention",
    "ring_attention",
    "selective_scan",
    "stack_stage_params",
    "streaming_ce",
    "ulysses_attention",
]
