"""JAX backend: the TPU-native replacement for the reference's Torch/NCCL
backend (train/torch/config.py:29 TorchConfig, :70
_setup_torch_process_group).

Where the reference calls ``dist.init_process_group(nccl)`` and lets DDP
allreduce gradients over NCCL, the JAX backend has three modes:

  "jax"   — multi-host SPMD: pick rank 0's host as coordinator, call
            ``jax.distributed.initialize(coordinator, n, rank)`` on every
            worker; each worker then sees the global TPU mesh and the
            train step's psum rides ICI inside jit.  (The TPU analog of
            the NCCL ring — but compiled into the program by XLA.)
  "store" — object-store collective group (ray_tpu.parallel.collective):
            gradients allreduce through shared memory.  Works anywhere
            (CPU tests, heterogeneous hosts); this is the
            ray.util.collective-parity path.
  "none"  — workers are independent (each jits over its own local
            devices; user syncs manually).

"auto" picks "jax" when workers hold TPU resources, else "store".
"""

from __future__ import annotations

import dataclasses
import socket
from typing import Optional

from ray_tpu.train.backend import Backend, BackendConfig


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    distributed: str = "auto"           # "auto"|"jax"|"store"|"none"
    coordinator_port: int = 0           # 0 = pick a free port
    virtual_devices: Optional[int] = None  # per-worker fake CPU devices
    group_name: str = "train"

    @property
    def backend_cls(self):
        return JaxBackend


def _pick_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _setup_virtual_devices(n: int):
    """Give this worker n virtual CPU jax devices (test mode; the analog
    of the reference's _fake_gpus)."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if f"--xla_force_host_platform_device_count={n}" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def _setup_jax_distributed(coordinator: str, num_processes: int,
                           process_id: int):
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def _setup_store_group(world_size: int, rank: int, group_name: str):
    from ray_tpu.parallel import collective

    collective.init_collective_group(world_size, rank,
                                     group_name=group_name)


def _get_node_ip() -> str:
    return socket.gethostbyname(socket.gethostname())


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        cfg = backend_config
        n = len(worker_group)

        if cfg.virtual_devices:
            worker_group.execute(_setup_virtual_devices,
                                 cfg.virtual_devices)

        mode = cfg.distributed
        if mode == "auto":
            # decide by what THESE workers were granted, not cluster totals
            worker_tpu = getattr(worker_group, "resources_per_worker",
                                 {}).get("TPU", 0)
            mode = "jax" if worker_tpu and n > 1 else \
                ("store" if n > 1 else "none")
        self.mode = mode

        if mode == "jax" and n > 1:
            ip = worker_group.execute_single(0, _get_node_ip)
            port = cfg.coordinator_port or \
                worker_group.execute_single(0, _pick_port)
            coordinator = f"{ip}:{port}"
            import ray_tpu

            ray_tpu.get([w.execute.remote(_setup_jax_distributed,
                                          coordinator, n, i)
                         for i, w in enumerate(worker_group.workers)],
                        timeout=120)
        elif mode == "store" and n > 1:
            import ray_tpu

            ray_tpu.get([w.execute.remote(_setup_store_group, n, i,
                                          cfg.group_name)
                         for i, w in enumerate(worker_group.workers)],
                        timeout=120)

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        def _teardown(group_name):
            from ray_tpu.parallel import collective

            if collective.is_group_initialized(group_name):
                collective.destroy_collective_group(group_name)

        try:
            worker_group.execute(_teardown, backend_config.group_name)
        except Exception:  # noqa: BLE001 - workers may be dead
            pass


def allreduce_gradients(grads, *, op: str = "mean",
                        group_name: str = "train"):
    """Allreduce a gradient pytree across the train worker group (store
    mode).  On a real multi-host mesh, use psum inside your jitted step
    instead — this helper is the CPU/heterogeneous path."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import collective

    leaves, treedef = jax.tree.flatten(grads)
    reduced = [jnp.asarray(collective.allreduce(leaf, op=op,
                                                group_name=group_name))
               for leaf in leaves]
    return jax.tree.unflatten(treedef, reduced)
