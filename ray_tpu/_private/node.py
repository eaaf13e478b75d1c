"""Node bootstrap: brings up the control-plane services for this host.

Role-equivalent of the reference's Node/services orchestration (reference
``python/ray/_private/node.py:41 class Node``, ``services.py:1204
start_gcs_server``, ``:1274 start_raylet``). Unlike the reference — which
forks separate gcs_server / raylet OS processes — the head's GCS and the
node manager are asyncio services on a dedicated IO thread inside the
driver process; worker processes are real subprocesses.  ``ray_tpu start``
(CLI) runs the same services standalone for multi-node clusters.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict, Optional

from ray_tpu._private.client import EventLoopThread
from ray_tpu._private.config import Config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.ids import NodeID
from ray_tpu._private.node_manager import NodeManager
from ray_tpu._private.object_store import ObjectStoreClient, default_shm_name


def detect_num_tpus(config: Config) -> int:
    """Count local TPU chips. ``num_tpus`` is a first-class predefined
    resource (the reference's GPU analog, scheduling_ids.h:34).

    A chip belongs to one process at a time and workers own it, not the
    driver, so the count is taken by a short-lived child that has
    released the chip again before any worker starts.  The child can
    only succeed while no other process holds the chip: a node started
    next to live TPU workers passes ``num_tpus`` (or sets
    ``tpu_chips_per_host``) instead of probing.

    A probe that fails or times out raises.  The only quiet zero is the
    caller's own: ``JAX_PLATFORMS=cpu``.
    """
    if config.tpu_chips_per_host:
        return config.tpu_chips_per_host
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() in ("cpu", "cpu,"):
        return 0
    import subprocess
    import sys

    code = ("import jax; "
            "print(len([d for d in jax.devices() "
            "if d.platform == 'tpu']))")
    hint = ("pass num_tpus= to init(), set tpu_chips_per_host, or pin "
            "JAX_PLATFORMS=cpu for a node without chips")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=config.tpu_detect_timeout_s)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(
            f"TPU detection timed out after "
            f"{config.tpu_detect_timeout_s:g}s; {hint}") from e
    if r.returncode != 0:
        raise RuntimeError(
            f"TPU detection failed (exit {r.returncode}): "
            f"{r.stderr.strip()[-400:]}; {hint}")
    return int(r.stdout.strip().splitlines()[-1])


def _gcs_is_local(gcs_address: str) -> bool:
    if gcs_address.startswith("/"):
        return True
    host = gcs_address.rsplit(":", 1)[0]
    return host in ("127.0.0.1", "localhost", "::1")


def _local_ip_toward(gcs_address: str) -> str:
    """This machine's IP on the route to the GCS (the address other
    nodes should dial us at)."""
    import socket

    host = gcs_address.rsplit(":", 1)[0]
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect((host, 1))  # no traffic; just picks the interface
            return s.getsockname()[0]
    except OSError:
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"


class Node:
    """One framework node. With ``head=True`` also hosts the GCS."""

    def __init__(self, *, head: bool = True,
                 num_cpus: Optional[int] = None,
                 num_tpus: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None,
                 object_store_memory: Optional[int] = None,
                 config: Optional[Config] = None,
                 gcs_address: str = "",
                 session_dir: str = "",
                 node_name: str = ""):
        self.config = config or Config().apply_env()
        self.head = head
        self.node_id = NodeID.from_random()
        sid = self.node_id.hex()[:8]
        self.session_dir = session_dir or f"/tmp/raytpu/s_{sid}"
        os.makedirs(os.path.join(self.session_dir, "sockets"), exist_ok=True)
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)
        if num_cpus is None:
            num_cpus = os.cpu_count() or 1
        if num_tpus is None:
            num_tpus = detect_num_tpus(self.config)
        self.resources: Dict[str, float] = {
            "CPU": float(num_cpus),
            "memory": float(object_store_memory or self.config.object_store_memory),
        }
        if num_tpus:
            self.resources["TPU"] = float(num_tpus)
        for k, v in (resources or {}).items():
            self.resources[k] = float(v)
        self.object_store_memory = int(
            object_store_memory or self.config.object_store_memory)
        self.shm_name = default_shm_name(f"{sid}_{os.getpid()}")
        self.gcs_address = gcs_address or os.path.join(
            self.session_dir, "sockets", "gcs")
        self.io: Optional[EventLoopThread] = None
        self.gcs: Optional[GcsServer] = None
        self.node_manager: Optional[NodeManager] = None
        self.store_owner: Optional[ObjectStoreClient] = None
        self._started = False

    def start(self):
        self.store_owner = ObjectStoreClient(
            self.shm_name, create=True, capacity=self.object_store_memory)
        self.io = EventLoopThread(name="raytpu-node")
        if self.head:
            self.gcs = GcsServer(
                heartbeat_timeout_s=self.config.heartbeat_interval_s
                * self.config.num_heartbeats_timeout,
                persist_path=self.config.gcs_persist_path)
            if self.gcs_address.startswith("/"):
                self.io.run(self.gcs.start_unix(self.gcs_address))
            else:
                host, port = self.gcs_address.rsplit(":", 1)
                real = self.io.run(self.gcs.start_tcp(host, int(port)))
                self.gcs_address = f"{host}:{real}"
        # Transport selection: unix sockets when the whole cluster lives
        # on this machine (GCS on a unix path or loopback); TCP when the
        # GCS is remote — a node manager advertising a unix path could
        # never be dialed by other machines for spillback leases or
        # chunked object pulls.
        node_address = ""
        if not _gcs_is_local(self.gcs_address):
            node_address = f"{_local_ip_toward(self.gcs_address)}:0"
        self.node_manager = NodeManager(
            self.node_id, self.session_dir, self.config,
            dict(self.resources), self.shm_name, self.gcs_address,
            node_address=node_address)
        self.io.run(self.node_manager.start())
        self._started = True
        return self

    @property
    def node_address(self) -> str:
        return self.node_manager.node_address

    def stop(self):
        if not self._started:
            return
        self._started = False
        try:
            self.io.run(self.node_manager.close(), timeout=10)
        except Exception:  # noqa: BLE001
            pass
        if self.gcs is not None:
            try:
                self.io.run(self.gcs.close(), timeout=10)
            except Exception:  # noqa: BLE001
                pass
        self.io.stop()
        try:
            self.store_owner.close(destroy=True)
        except Exception:  # noqa: BLE001
            pass
        shutil.rmtree(os.path.join(self.session_dir, "sockets"),
                      ignore_errors=True)
