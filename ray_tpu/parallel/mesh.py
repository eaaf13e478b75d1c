"""Mesh construction: named parallelism axes over TPU device grids.

The reference scales training with NCCL process groups
(train/torch/config.py:70 `dist.init_process_group`); the TPU-native
equivalent is a `jax.sharding.Mesh` whose named axes carry the
parallelism strategy.  One mesh, five standard axes:

  data    — pure data parallelism (gradients psum over it)
  fsdp    — data parallelism with ZeRO-3 weight sharding
  tensor  — tensor (op-level) parallelism, Megatron-style
  seq     — sequence/context parallelism (ring attention)
  expert  — expert parallelism for MoE layers
  (pipeline — stage axis for pipeline parallelism, ray_tpu.ops.pipeline)

Multi-slice jobs get a hybrid mesh: DCN-connected axes outermost (data
replication across slices), ICI axes inner — so the bandwidth-hungry
collectives (fsdp all-gather, tp all-reduce) ride ICI.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_PIPELINE = "pipeline"

# Canonical axis order: replication-heavy (DCN-tolerant) outermost,
# bandwidth-hungry (ICI-needing) innermost.
_AXIS_ORDER = (AXIS_PIPELINE, AXIS_DATA, AXIS_FSDP, AXIS_EXPERT, AXIS_SEQ,
               AXIS_TENSOR)


@dataclasses.dataclass
class MeshSpec:
    """Declarative mesh request.  -1 on at most one axis means "absorb
    all remaining devices" (like a reshape wildcard)."""

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipeline: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {AXIS_PIPELINE: self.pipeline, AXIS_DATA: self.data,
                AXIS_FSDP: self.fsdp, AXIS_EXPERT: self.expert,
                AXIS_SEQ: self.seq, AXIS_TENSOR: self.tensor}

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = self.axis_sizes()
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {known}")
            sizes[wild[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh spec {sizes} needs {known} devices, have {n_devices}")
        return MeshSpec(data=sizes[AXIS_DATA], fsdp=sizes[AXIS_FSDP],
                        tensor=sizes[AXIS_TENSOR], seq=sizes[AXIS_SEQ],
                        expert=sizes[AXIS_EXPERT],
                        pipeline=sizes[AXIS_PIPELINE])

    def nontrivial_axes(self) -> List[Tuple[str, int]]:
        sizes = self.axis_sizes()
        return [(a, sizes[a]) for a in _AXIS_ORDER if sizes[a] != 1]

    @property
    def n_devices(self) -> int:
        return math.prod(self.axis_sizes().values())


def _import_jax():
    import jax
    from jax.sharding import Mesh
    return jax, Mesh


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence] = None,
              *, contiguous_submeshes: bool = False):
    """Build a Mesh with all six named axes (trivial axes have size 1 so
    PartitionSpecs naming any standard axis always resolve).

    Uses `mesh_utils.create_device_mesh` so the device order follows the
    physical ICI torus coordinates rather than enumeration order —
    neighbor exchanges (ring attention ppermute, pipeline transfers) hit
    single-hop ICI links.
    """
    jax, Mesh = _import_jax()
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    spec = (spec or MeshSpec(data=-1)).resolve(len(devices))
    shape = tuple(spec.axis_sizes()[a] for a in _AXIS_ORDER)
    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            contiguous_submeshes=contiguous_submeshes)
    except (ValueError, AssertionError, NotImplementedError):
        # CPU/fake platforms have no topology; plain reshape.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, _AXIS_ORDER)


def make_hybrid_mesh(spec: MeshSpec, *, num_slices: int,
                     devices: Optional[Sequence] = None):
    """Multi-slice mesh: DCN axes (pipeline, data) across slices, ICI
    axes within each slice (jax mesh_utils.create_hybrid_device_mesh).
    The `data` (or `pipeline`) axis size must be divisible by num_slices.
    """
    jax, Mesh = _import_jax()
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    spec = spec.resolve(len(devices))
    sizes = spec.axis_sizes()
    dcn_sizes, ici_sizes = [], []
    remaining_dcn = num_slices
    for a in _AXIS_ORDER:
        s = sizes[a]
        if remaining_dcn > 1 and s % remaining_dcn == 0 and a in (
                AXIS_PIPELINE, AXIS_DATA, AXIS_FSDP):
            dcn_sizes.append(remaining_dcn)
            ici_sizes.append(s // remaining_dcn)
            remaining_dcn = 1
        else:
            dcn_sizes.append(1)
            ici_sizes.append(s)
    if remaining_dcn != 1:
        raise ValueError(
            f"cannot place {num_slices} slices on axes {sizes}; make "
            f"pipeline/data/fsdp divisible by num_slices")
    try:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_sizes), tuple(dcn_sizes), devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(
            tuple(d * i for d, i in zip(dcn_sizes, ici_sizes)))
    return Mesh(dev_array, _AXIS_ORDER)


def active_mesh():
    """The concrete Mesh made current by ``jax.set_mesh``, or None when
    no mesh is active.  All mesh-sensitive dispatch in this repo
    (logical constraints, flash and ring attention, pipeline stages)
    asks here, so there is one definition of "a mesh is active"."""
    from jax._src.mesh import get_concrete_mesh

    m = get_concrete_mesh()
    return None if m is None or m.empty else m


def local_mesh(spec: Optional[MeshSpec] = None):
    """Mesh over this process's addressable devices only."""
    jax, _ = _import_jax()
    return make_mesh(spec, devices=jax.local_devices())


def fake_mesh(n_devices: int = 8, spec: Optional[MeshSpec] = None):
    """Test mesh over virtual CPU devices.

    Requires XLA_FLAGS=--xla_force_host_platform_device_count=N (set in
    tests/conftest.py) — the TPU analog of the reference's `_fake_gpus`
    (rllib/algorithms/algorithm_config.py:344).
    """
    jax, _ = _import_jax()
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"fake_mesh({n_devices}) needs "
            f"xla_force_host_platform_device_count>={n_devices}; "
            f"have {len(devices)}")
    return make_mesh(spec or MeshSpec(data=n_devices),
                     devices=devices[:n_devices])
