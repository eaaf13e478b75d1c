"""Model zoo: TPU-first reference models driven by ray_tpu.train.

Pure-functional JAX (init/apply pairs over pytrees), layers stacked for
`lax.scan`, parameters annotated with logical sharding axes
(ray_tpu.parallel.sharding) so one model definition serves DP, FSDP, TP,
and sequence parallelism by swapping the rule table.
"""

from ray_tpu.models.gpt2 import (GPT2Config, gpt2_config, gpt2_forward,
                                 gpt2_init, gpt2_logical_axes, gpt2_loss,
                                 gpt2_param_count)
from ray_tpu.models.gpt2_decode import (decode_step, generate,
                                        init_cache, prefill)
from ray_tpu.models.jamba import (JambaConfig, jamba_config,
                                  jamba_forward, jamba_init,
                                  jamba_logical_axes, jamba_loss,
                                  jamba_param_count)
from ray_tpu.models.jamba_decode import (jamba_decode_step,
                                         jamba_generate,
                                         jamba_init_cache,
                                         jamba_prefill)
from ray_tpu.models.llama import (LlamaConfig, llama_config,
                                  llama_forward, llama_init,
                                  llama_logical_axes, llama_loss,
                                  llama_param_count)
from ray_tpu.models.llama_decode import (llama_decode_step,
                                         llama_generate,
                                         llama_init_cache,
                                         llama_prefill)
from ray_tpu.models.moe import (MoEConfig, moe_apply, moe_init,
                                moe_logical_axes)
from ray_tpu.models.mlp import (MLPConfig, mlp_forward, mlp_init,
                                mlp_logical_axes, mlp_loss)
from ray_tpu.models.resnet import (ResNetConfig, resnet_config,
                                   resnet_forward, resnet_init,
                                   resnet_logical_axes, resnet_loss)
from ray_tpu.models.vit import (ViTConfig, vit_config, vit_forward,
                                vit_init, vit_logical_axes, vit_loss,
                                vit_param_count)

__all__ = [
    "GPT2Config", "gpt2_config", "gpt2_init", "gpt2_forward", "gpt2_loss",
    "gpt2_logical_axes", "gpt2_param_count", "init_cache", "decode_step",
    "generate", "prefill",
    "MLPConfig", "mlp_init", "mlp_forward", "mlp_loss", "mlp_logical_axes",
    "MoEConfig", "moe_init", "moe_apply", "moe_logical_axes",
    "ResNetConfig", "resnet_config", "resnet_init", "resnet_forward",
    "resnet_loss", "resnet_logical_axes",
    "ViTConfig", "vit_config", "vit_init", "vit_forward", "vit_loss",
    "vit_logical_axes", "vit_param_count",
    "LlamaConfig", "llama_config", "llama_init", "llama_forward",
    "llama_loss", "llama_logical_axes", "llama_param_count",
    "llama_init_cache", "llama_decode_step", "llama_generate",
    "llama_prefill",
    "JambaConfig", "jamba_config", "jamba_init", "jamba_forward",
    "jamba_loss", "jamba_logical_axes", "jamba_param_count",
    "jamba_init_cache", "jamba_decode_step", "jamba_generate",
    "jamba_prefill",
]
