"""Global device-mesh management.

The reference's communicator registries (platform/collective_helper.h: per-ring
NCCLCommContext) become ONE logical object on TPU: a jax.sharding.Mesh whose
named axes are the parallelism dimensions. Groups (collective.py) and the fleet
topology (fleet/base/topology.py analog) are views onto these axes; XLA emits
the matching ICI/DCN collectives from sharding specs.

Axis order follows the reference's hybrid topology
(fleet/base/topology.py:38): ["data", "pipe", "sharding", "sep", "model"].
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical axis names, reference order topology.py:38 (+ net-new "sep")
AXIS_DATA = "data"
AXIS_PIPE = "pipe"
AXIS_SHARD = "sharding"
AXIS_SEP = "sep"
AXIS_MODEL = "model"
# expert parallelism (MoE): not part of the hybrid order — built
# explicitly via build_mesh({"expert": k, ...}). Declared HERE so every
# axis name the framework can route a collective over has one source of
# truth (rule X005 validates axis strings against these constants).
AXIS_EXPERT = "expert"
HYBRID_ORDER = [AXIS_DATA, AXIS_PIPE, AXIS_SHARD, AXIS_SEP, AXIS_MODEL]

_current: List[Optional[Mesh]] = [None]


def build_mesh(topology: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Create a Mesh from {axis: degree}. Missing hybrid axes get degree 1 and
    are dropped; axis order follows HYBRID_ORDER then any custom names."""
    devices = list(devices if devices is not None else jax.devices())
    names, dims = [], []
    for ax in HYBRID_ORDER:
        d = int(topology.get(ax, 1))
        if d > 1 or ax in topology:
            names.append(ax)
            dims.append(d)
    for ax, d in topology.items():
        if ax not in HYBRID_ORDER:
            names.append(ax)
            dims.append(int(d))
    total = int(np.prod(dims)) if dims else 1
    if total != len(devices):
        raise ValueError(
            f"mesh topology {dict(zip(names, dims))} needs {total} devices, "
            f"have {len(devices)}"
        )
    arr = np.array(devices).reshape(dims if dims else (1,))
    if not names:
        names = [AXIS_DATA]
    return Mesh(arr, tuple(names))


def set_mesh(mesh: Mesh):
    _current[0] = mesh
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _current[0]


def default_mesh() -> Mesh:
    """All devices on the data axis (pure DP)."""
    if _current[0] is None:
        set_mesh(build_mesh({AXIS_DATA: len(jax.devices())}))
    return _current[0]


def axis_size(axis: str) -> int:
    m = get_mesh()
    if m is None or axis not in m.axis_names:
        return 1
    return m.shape[axis]


def compat_shard_map(fn, mesh, in_specs, out_specs, check=False):
    """jax.shard_map with replication tracking (`check_vma`) off by default:
    most collective-bearing bodies manage their own replication (the 1F1B
    grad path is the exception, see pipeline/schedule.py)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(default_mesh(), PartitionSpec(*spec))


def shard_tensor_value(val, spec: PartitionSpec):
    """Place a value onto the current mesh with the given PartitionSpec."""
    return jax.device_put(val, NamedSharding(default_mesh(), spec))


def sanitize_spec(spec: PartitionSpec, mesh: Optional[Mesh] = None) -> PartitionSpec:
    """Drop axis names not present in the mesh so model code can annotate the
    full hybrid spec [data, pipe, sharding, sep, model] unconditionally."""
    mesh = mesh or get_mesh()
    if mesh is None or spec is None:
        return spec or PartitionSpec()
    names = mesh.axis_names
    out = []
    for s in spec:
        if isinstance(s, str):
            out.append(s if s in names else None)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if a in names)
            out.append(kept if kept else None)
        else:
            out.append(s)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def manual_axis_names() -> set:
    """Axis names currently bound MANUALLY (inside a shard_map/pmap body):
    a sharding constraint over such an axis is invalid — the body already
    sees its per-device block — so constrain() drops them."""
    return set(jax.sharding.get_abstract_mesh().manual_axes)


def constrain(tensor, *spec):
    """Sharding constraint on a Tensor while tracing under a mesh; no-op
    eagerly or without a mesh. Axes absent from the mesh — and axes the
    surrounding trace already maps manually (a shard_map body, e.g. the
    explicit-SPMD grad path of jit.TrainStep(grad_comm=)) — are dropped,
    so model code can annotate the full hybrid spec unconditionally."""
    m = get_mesh()
    if m is None:
        return tensor
    from ..framework.autograd import call_op
    from ..framework.tensor import Tensor

    if isinstance(tensor, Tensor) and not isinstance(tensor._value, jax.core.Tracer):
        return tensor
    clean = sanitize_spec(PartitionSpec(*spec), m)
    manual = manual_axis_names()
    if manual:
        drop = []
        for entry in clean:
            if isinstance(entry, str) and entry in manual:
                entry = None
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                entry = kept if kept else None
            drop.append(entry)
        while drop and drop[-1] is None:
            drop.pop()
        clean = PartitionSpec(*drop)
        if not tuple(clean):
            return tensor   # nothing left to constrain inside the body
    sh = NamedSharding(m, clean)
    return call_op(lambda v: jax.lax.with_sharding_constraint(v, sh), tensor,
                   op_name="shard_constraint")
