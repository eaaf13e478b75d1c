"""GPT-2 train smoke: loss must decrease over real optimizer steps (on
the TPU; CPU-tiny only when the caller set JAX_PLATFORMS=cpu)."""
import json
import os

import bench  # repo-root bench: fails when neither holds

bench.require_backend()
import jax

size = "tiny"
steps = 8
if jax.default_backend() == "tpu" and not os.environ.get("RELEASE_FAST"):
    size, steps = "gpt2", 20

import functools

import jax.numpy as jnp
import optax

from ray_tpu.models import gpt2_config, gpt2_init, gpt2_loss

cfg = gpt2_config(size, use_flash=False)
params = gpt2_init(jax.random.PRNGKey(0), cfg)
tx = optax.adamw(3e-4)
opt = tx.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, cfg.max_seq + 1),
                            0, cfg.vocab_size)

@jax.jit
def step(p, o):
    l, g = jax.value_and_grad(lambda p: gpt2_loss(p, {"tokens": tokens},
                                                  cfg))(p)
    up, o = tx.update(g, o, p)
    return optax.apply_updates(p, up), o, l

losses = []
for _ in range(steps):
    params, opt, loss = step(params, opt)
    losses.append(float(loss))
print(json.dumps({"first_loss": losses[0], "last_loss": losses[-1],
                  "loss_decreased": losses[-1] < losses[0]}))
