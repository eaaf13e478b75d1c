"""JaxTrainer — the flagship trainer (BASELINE.json north star: "Ray
Train's TorchTrainer/DataParallelTrainer gains a JaxTrainer whose
BackendConfig initializes jax.distributed and maps the NCCL allreduce to
XLA collectives over ICI").

DataParallelTrainer + JaxConfig, plus worker-side helpers that replace
the reference's ``prepare_model`` DDP/FSDP wrapping
(train/torch/train_loop_utils.py:28,72-114) with mesh/sharding setup:

    def loop(cfg):
        mesh = jax_utils.get_mesh()                # worker's device mesh
        params = jax_utils.shard_pytree(params, axes, mesh)
        step = jax_utils.build_train_step(loss_fn, tx, mesh, axes)
        ...
        session.report({"loss": l}, checkpoint=...)
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer
from ray_tpu.train.jax_backend import JaxConfig


class JaxTrainer(DataParallelTrainer):
    _default_backend_config = JaxConfig()

    def __init__(self, train_loop_per_worker: Callable, *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 jax_config: Optional[JaxConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint=None):
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            backend_config=jax_config or JaxConfig(),
            scaling_config=scaling_config, run_config=run_config,
            datasets=datasets,
            resume_from_checkpoint=resume_from_checkpoint)


class jax_utils:
    """Worker-side helpers (importable functions grouped for discovery)."""

    @staticmethod
    def get_mesh(spec=None):
        """Mesh over this worker's addressable devices (single-host) or
        the global mesh (jax.distributed mode)."""
        from ray_tpu.parallel import make_mesh

        return make_mesh(spec)

    @staticmethod
    def shard_pytree(tree, logical_axes, mesh, rules=None):
        from ray_tpu.parallel import sharding

        return sharding.shard_params(
            tree, logical_axes, mesh,
            rules=rules or sharding.DEFAULT_RULES)

    @staticmethod
    def build_train_step(loss_fn, tx, mesh=None, logical_axes=None,
                         rules=None, donate: bool = True,
                         telemetry: bool = True,
                         telemetry_name: str = "jax_trainer",
                         health: bool = False):
        """jitted (params, opt_state, batch) -> (params, opt_state, loss)
        with optional sharding constraints from logical_axes.

        telemetry=True (default) wraps the step with host-side
        step-time histograms, examples/sec gauges, compile-event
        counters (train/telemetry.py — perf_counter pairs only, no
        added device syncs) AND the trainwatch anatomy/goodput
        recorder (train/goodput.py); read them back via
        ``jax_utils.train_stats(telemetry_name)``.

        health=True makes the step additionally return cheap device
        scalars as a 4th output — ``{"loss", "grad_norm",
        "nonfinite"}``, all computed INSIDE the jitted program (no
        extra dispatch, no host transfer in the jaxpr) — and arms the
        host-side watchdog: EWMA z-score spikes and NaN/inf trip a
        ``train_anomaly`` journal event plus a flight-recorder
        postmortem naming the step, trainer, and batch signature.
        Reading the scalars fences each step (one small D2H), which
        is what buys one-step detection latency."""
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu._private import scopes
        from ray_tpu.parallel import sharding

        in_shardings = None
        if mesh is not None and logical_axes is not None:
            p_shard = sharding.param_shardings(
                logical_axes, mesh, rules or sharding.DEFAULT_RULES)
            in_shardings = (p_shard, None, None)

        def step(params, opt_state, batch):
            # scopes are metadata on the program's instructions; the
            # model's own (attn, mlp, ...) nest under loss_and_grad
            with jax.named_scope(scopes.LOSS_AND_GRAD):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            if not health:
                return new_params, opt_state, loss
            nonfinite = functools.reduce(
                jnp.add,
                [jnp.sum(~jnp.isfinite(g)).astype(jnp.int32)
                 for g in jax.tree_util.tree_leaves(grads)],
                jnp.int32(0))
            scalars = {"loss": loss,
                       "grad_norm": optax.global_norm(grads),
                       "nonfinite": nonfinite}
            return new_params, opt_state, loss, scalars

        kw: Dict[str, Any] = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if donate:
            kw["donate_argnums"] = (0, 1)
        jitted = jax.jit(step, **kw)
        if not telemetry:
            return jitted
        from ray_tpu._private.device_stats import get_registry
        from ray_tpu.train.goodput import (get_goodput_tracker,
                                           get_health_watchdog,
                                           instrument_trainwatch)
        from ray_tpu.train.telemetry import (get_train_telemetry,
                                             instrument_train_step)

        # perf observatory first (compiled-cost harvest + recompile
        # watchdog under "train.step"), host step-time telemetry next,
        # trainwatch anatomy/health on the outside — all are
        # signature-keyed; only health mode adds a (deliberate) sync
        n_dev = int(mesh.size) if mesh is not None else 1
        jitted = get_registry().instrument("train.step", jitted,
                                           n_devices=n_dev)
        jitted = instrument_train_step(
            jitted, telemetry=get_train_telemetry(telemetry_name))
        wrapped = instrument_trainwatch(
            jitted,
            tracker=get_goodput_tracker(telemetry_name),
            watchdog=(get_health_watchdog(telemetry_name)
                      if health else None))
        wrapped._raw_step = step   # the jaxpr-guard hook (tests)
        return wrapped

    @staticmethod
    def train_stats(name: str = "jax_trainer"):
        """Step-time percentiles / compile counts recorded by
        ``build_train_step`` steps in THIS process (workers call it
        inside the loop and ``session.report`` it up)."""
        from ray_tpu.train.telemetry import train_stats

        return train_stats(name)

    @staticmethod
    def allreduce_gradients(grads, op: str = "mean",
                            group_name: str = "train"):
        from ray_tpu.train.jax_backend import allreduce_gradients

        return allreduce_gradients(grads, op=op, group_name=group_name)
