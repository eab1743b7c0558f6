"""Named device-mesh construction — the TPU-native replacement for process groups.

Reference parity (SURVEY.md §2d): the reference's communication substrate is a
c10d ``ProcessGroup`` over NCCL, created by ``init_process_group('nccl')``.
On TPU the substrate is the XLA partitioner over a :class:`jax.sharding.Mesh`:
you never hand-write transport code — you declare *named axes* and shardings
and XLA emits ICI/DCN collectives inside the compiled step.

Axis convention (DCN-major ordering — the outermost axis crosses the slowest
interconnect, so pure data-parallel gradient reduction is what rides DCN in
multislice, while TP/CP collectives stay on ICI):

    data    — pure data parallelism (gradient psum; replicated params)
    fsdp    — data parallelism with parameter/optimizer sharding (ZeRO-3)
    stage   — pipeline-parallel stage axis
    expert  — MoE expert parallelism
    context — sequence/context parallelism (ring attention / Ulysses)
    model   — tensor (Megatron-style) model parallelism

A batch is sharded over ``('data','fsdp')`` jointly; any axis of size 1 is
free (GSPMD ignores it), so one 6-axis mesh serves every strategy.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("pdtx")

AXES: tuple[str, ...] = ("data", "fsdp", "stage", "expert", "context", "model")

#: Axes over which the batch dimension is sharded (both are "data parallel"
#: axes from the input pipeline's point of view).
BATCH_AXES: tuple[str, ...] = ("data", "fsdp")

#: Spelling aliases accepted in mesh-spec dicts (CLI ``--mesh seq=4``,
#: SNIPPETS.md [3]'s rules vocabulary). The canonical axis names stay AXES —
#: aliases are normalized before MeshConfig is built so every downstream
#: consumer (rule tables, shard_map axis names, the AOT census) sees one
#: spelling.
AXIS_ALIASES: dict[str, str] = {"seq": "context", "cp": "context",
                                "tp": "model", "ep": "expert",
                                "pp": "stage"}


def normalize_axes(spec: dict) -> dict:
    """Map aliased axis names in a mesh-spec dict onto the canonical AXES.

    Raises when an alias and its canonical name are both given (ambiguous
    intent beats a silent override).
    """
    out: dict = {}
    for key, val in spec.items():
        canon = AXIS_ALIASES.get(key, key)
        if canon in out:
            raise ValueError(
                f"mesh spec names axis {canon!r} twice (via {key!r}); "
                f"aliases: {AXIS_ALIASES}")
        out[canon] = val
    return out


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. ``data=-1`` absorbs all remaining devices.

    The product of all axis sizes must equal the device count (after ``-1``
    expansion). This mirrors how the reference picks ``world_size`` from the
    launcher (SURVEY.md §3.1) — here the "world" is the device mesh.
    """

    data: int = -1
    fsdp: int = 1
    stage: int = 1
    expert: int = 1
    context: int = 1
    model: int = 1

    def sizes(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.stage, self.expert, self.context, self.model)

    def resolve(self, num_devices: int) -> tuple[int, ...]:
        sizes = list(self.sizes())
        fixed = math.prod(s for s in sizes if s != -1)
        n_wild = sum(1 for s in sizes if s == -1)
        if n_wild > 1:
            raise ValueError("at most one mesh axis may be -1")
        if n_wild == 1:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[sizes.index(-1)] = num_devices // fixed
        if math.prod(sizes) != num_devices:
            raise ValueError(
                f"mesh {dict(zip(AXES, sizes))} needs {math.prod(sizes)} devices, "
                f"have {num_devices}"
            )
        return tuple(sizes)

    def elastic_resolve(self, num_devices: int) -> tuple[int, ...]:
        """:meth:`resolve`, but degrade pinned axes when the device set shrank.

        Elastic resume relaunches with fewer (or more) devices than the mesh
        was configured for. A wildcard axis absorbs the change for free; when
        the *fixed* axes no longer fit, shrink each — innermost (``model``)
        first, since inner axes carry the latency-sensitive collectives that
        a degraded topology can least afford — to its largest divisor that
        still fits, and let ``data`` (or the wildcard) absorb the remainder.
        Changes are logged loudly; the result always multiplies out to
        ``num_devices``.
        """
        try:
            return self.resolve(num_devices)
        except ValueError:
            pass
        sizes = list(self.sizes())
        wild = sizes.index(-1) if -1 in sizes else 0
        if sizes[wild] == -1:
            sizes[wild] = 1
        # Shrink fixed axes innermost-first until the rest fits.
        for i in reversed(range(len(sizes))):
            if i == wild:
                continue
            others = math.prod(s for j, s in enumerate(sizes)
                               if j != i and j != wild)
            cap = max(1, num_devices // others)
            sizes[i] = math.gcd(sizes[i], cap)
        others = math.prod(s for j, s in enumerate(sizes) if j != wild)
        if num_devices % others:
            raise ValueError(
                f"elastic resolve failed: fixed axes "
                f"{dict(zip(AXES, sizes))} do not divide {num_devices} devices")
        sizes[wild] = num_devices // others
        resolved = tuple(sizes)
        changed = {a: (old, new) for a, old, new
                   in zip(AXES, self.sizes(), resolved)
                   if old not in (-1, new)}
        if changed:
            log.warning(
                "elastic mesh: %d devices cannot satisfy the configured mesh "
                "— degraded axes %s (full shape %s)", num_devices,
                {a: f"{o}->{n}" for a, (o, n) in changed.items()},
                dict(zip(AXES, resolved)))
        return resolved


def dcn_split(shape: Sequence[int], num_slices: int) -> tuple[tuple, tuple]:
    """Split a logical mesh shape into (per-slice ICI shape, DCN shape).

    Multislice rule (SURVEY.md §2d): the slice dimension — the only traffic
    that crosses DCN — must land on the OUTERMOST data-parallel axis whose
    size it divides (``data`` first, then ``fsdp``), so gradient psum is
    what rides DCN while TP/CP/EP collectives stay on intra-slice ICI.
    """
    dcn = [1] * len(shape)
    for i in (0, 1):  # data, fsdp
        if shape[i] % num_slices == 0:
            dcn[i] = num_slices
            break
    else:
        raise ValueError(
            f"multislice with {num_slices} slices needs a data or fsdp axis "
            f"divisible by it; mesh is {dict(zip(AXES, shape))}")
    ici = tuple(s // d for s, d in zip(shape, dcn))
    return ici, tuple(dcn)


def build_mesh(
    config: MeshConfig | dict | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
    elastic: bool = False,
) -> Mesh:
    """Build the named device mesh.

    Uses ``mesh_utils.create_device_mesh`` so the logical mesh is laid out
    along the physical ICI torus (nearest-neighbor axes get the fastest
    links); multislice device sets (distinct ``slice_index``) go through
    ``create_hybrid_device_mesh`` with the slice dimension on the outermost
    data axis (DCN-major). Falls back to a plain reshape for CPU/fake
    devices.
    """
    if config is None:
        config = MeshConfig()
    elif isinstance(config, dict):
        config = MeshConfig(**normalize_axes(config))
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    shape = (config.elastic_resolve(len(devices)) if elastic
             else config.resolve(len(devices)))
    slices = {getattr(d, "slice_index", 0) for d in devices}
    if len(slices) > 1:
        ici, dcn = dcn_split(shape, len(slices))  # config errors surface
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices)
    else:
        from jax.experimental import mesh_utils

        try:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except Exception:
            # Fake host devices have no torus to lay out along; on a real
            # chip a failed layout is a fault, not something to paper over.
            if devices[0].platform != "cpu":
                raise
            dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """1-device mesh (the reference's non-``--distributed`` path, SURVEY.md §3.5)."""
    if device is None:
        device = jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1,) * len(AXES)), AXES)


# ---------------------------------------------------------------------------
# Current-mesh context: lets model code apply sharding constraints without
# threading the mesh through every call signature.
# ---------------------------------------------------------------------------

_local = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make ``mesh`` the ambient mesh for :func:`constrain` and friends."""
    prev = current_mesh()
    _local.mesh = mesh
    try:
        yield mesh
    finally:
        _local.mesh = prev


@contextlib.contextmanager
def no_constrain():
    """Disable :func:`constrain` in this trace region.

    Used when model code runs inside ``shard_map`` (pipeline stages), where
    values are per-device and global sharding constraints don't apply.
    """
    prev = getattr(_local, "constrain_disabled", False)
    _local.constrain_disabled = True
    try:
        yield
    finally:
        _local.constrain_disabled = prev


def constrain(x, spec: P):
    """``with_sharding_constraint`` against the ambient mesh (no-op without one).

    Drops axis names that the ambient mesh does not have at size > 1, so model
    code can always annotate the "full" spec (e.g. activations sharded over
    ``('data','fsdp')`` and ``'model'``) and run unmodified on any mesh shape.
    """
    mesh = current_mesh()
    if mesh is None or getattr(_local, "constrain_disabled", False):
        return x
    spec = _prune_spec(spec, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def manual_call(fn, *args, in_specs, out_specs, mesh: Mesh | None = None):
    """Run ``fn`` per device of ``mesh`` — the ambient mesh by default —
    through a ``shard_map`` over ALL its axes.

    Mosaic kernels cannot be partitioned by GSPMD: lowered bare under a
    multi-device mesh the chip's compiler refuses them ("wrap the call in a
    shard_map"), which no CPU run ever meets. Every Pallas call site on a
    GSPMD path goes through here: mesh axes the specs leave unmentioned
    replicate, so ``P()`` specs mean "every device runs the whole kernel".
    Without a multi-device mesh, or when already inside a manual
    region (ring attention, pipeline stages), ``fn`` is
    called directly.
    """
    mesh = mesh or current_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _prune_spec(spec: P, mesh: Mesh) -> P:
    def keep(axis):
        return mesh.shape.get(axis, 1) > 1

    pruned = []
    for entry in spec:
        if entry is None:
            pruned.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if keep(a))
            pruned.append(kept if kept else None)
        else:
            pruned.append(entry if keep(entry) else None)
    return P(*pruned)


# ---------------------------------------------------------------------------
# Batch sharding (the DistributedSampler/DataLoader device-side contract)
# ---------------------------------------------------------------------------


def batch_pspec(ndim: int = 1) -> P:
    """PartitionSpec sharding axis 0 (batch) over the data-parallel axes."""
    return P(BATCH_AXES, *([None] * (ndim - 1)))


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(ndim))


def dp_size(mesh: Mesh) -> int:
    """Total data-parallel degree (replicas of the model across the batch)."""
    return mesh.shape["data"] * mesh.shape["fsdp"]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
