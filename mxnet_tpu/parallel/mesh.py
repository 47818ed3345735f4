"""Device mesh helpers.

The TPU-native replacement for the reference's device topology machinery
(src/kvstore/gpu_topology.h link discovery, CommDeviceTree): on TPU the
topology is a named mesh and XLA chooses collective algorithms over ICI/DCN.
Axis convention (scaling-book style): 'dp' data, 'fsdp' fully-sharded data,
'tp' tensor/model, 'pp' pipeline, 'sp' sequence/context, 'ep' expert.
"""
from __future__ import annotations

import math
import os

import numpy as np

__all__ = ["create_mesh", "default_mesh", "named_mesh", "parse_mesh_spec",
           "local_devices", "shrink_mesh", "MeshShrinkError", "AXES",
           "PodTopology", "pod_mesh", "shrink_mesh_hosts"]

AXES = ("dp", "fsdp", "tp", "pp", "sp", "ep")


def local_devices(platform=None):
    import jax

    return jax.devices(platform) if platform else jax.devices()


def create_mesh(axes=None, devices=None):
    """Create a jax.sharding.Mesh.

    axes: dict axis-name -> size (a -1 size absorbs remaining devices),
          or None for a pure data-parallel mesh over all devices.
    """
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"dp": len(devices)}
    names = list(axes)
    sizes = [axes[n] for n in names]
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        sizes[sizes.index(-1)] = len(devices) // known
    total = math.prod(sizes)
    assert total == len(devices), \
        f"mesh {dict(zip(names, sizes))} needs {total} devices, " \
        f"got {len(devices)}"
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


class MeshShrinkError(RuntimeError):
    """No viable smaller mesh exists after excising the dead ranks.

    Structured: carries the old mesh shape (``axes``), the ranks that
    died (``dead_ranks``) and the axis that was being shrunk
    (``batch_axis``) so recovery code and crash reports can say exactly
    why the topology could not be rebuilt.
    """

    def __init__(self, msg, *, axes=None, dead_ranks=(), batch_axis=None):
        super().__init__(msg)
        self.axes = dict(axes or {})
        self.dead_ranks = tuple(dead_ranks)
        self.batch_axis = batch_axis


def shrink_mesh(mesh, dead_ranks, batch_axis="dp"):
    """The largest viable mesh buildable from the survivors after losing
    ``dead_ranks`` along the (data-parallel) shrink axis — the topology
    half of elastic peer-loss recovery (resilience/elastic.py; the state
    half is the reshardable checkpoint restore).

    ``batch_axis`` may be one axis name or a tuple of names (the batch
    dimension of a dp×fsdp mesh is sharded over both); shrinking always
    happens along the FIRST name — the outermost data axis — and every
    other axis keeps its full extent, because losing a dp peer must not
    silently change the fsdp/tp layout the parameters are sharded over.

    On a one-axis mesh a rank IS its slot coordinate. On a multi-axis
    mesh a rank is the flat device ordinal in ``mesh.devices`` (C
    order): its shrink-axis coordinate names the slot lost, and the
    WHOLE slot — the full fsdp×tp slice that peer participated in — is
    excised. Ranks outside the device range still cost a slot each,
    dropped from the tail. The new extent is the largest power of two
    that fits the survivors, so dp=8 degrades 8 -> 4 -> 2 -> 1 and
    batch divisibility (rows % dp) is preserved for power-of-two
    batches. Raises a structured MeshShrinkError when the survivors
    cannot rebuild a mesh that still tiles the non-batch axes.
    """
    from jax.sharding import Mesh

    names = list(mesh.axis_names)
    old_axes = dict(zip(names, mesh.devices.shape))
    shrink_axes = ((batch_axis,) if isinstance(batch_axis, str)
                   else tuple(batch_axis))
    shrink_axis = shrink_axes[0]
    if shrink_axis not in names:
        raise MeshShrinkError(
            f"mesh {names} has no '{shrink_axis}' axis to shrink",
            axes=old_axes, dead_ranks=dead_ranks, batch_axis=shrink_axis)
    axis = names.index(shrink_axis)
    size = int(mesh.devices.shape[axis])
    dead = {int(r) for r in dead_ranks}
    if not dead:
        raise MeshShrinkError("no dead ranks to excise",
                              axes=old_axes, batch_axis=shrink_axis)
    total = int(mesh.devices.size)
    if total == size:  # one-axis fast path: rank IS the slot coordinate
        in_range = sorted(r for r in dead if 0 <= r < size)
        lost_slots = set(in_range)
        extra = len(dead) - len(in_range)
    else:  # multi-axis: rank = flat device ordinal -> shrink-axis slot
        in_range = sorted(r for r in dead if 0 <= r < total)
        lost_slots = {
            int(np.unravel_index(r, mesh.devices.shape)[axis])
            for r in in_range}
        extra = len(dead) - len(in_range)
    slots = [i for i in range(size) if i not in lost_slots]
    if extra:  # ranks we can't map onto the axis still each cost a slot
        slots = slots[:max(0, len(slots) - extra)]
    non_batch = {n: s for n, s in old_axes.items() if n != shrink_axis}
    if not slots:
        raise MeshShrinkError(
            f"all {size} '{shrink_axis}' slots lost ranks; no survivors "
            "to rebuild a mesh from"
            + (f" (non-batch axes {non_batch} left untiled)"
               if non_batch else ""),
            axes=old_axes, dead_ranks=dead_ranks, batch_axis=shrink_axis)
    new_size = 1 << (len(slots).bit_length() - 1)
    if new_size >= size:
        raise MeshShrinkError(
            f"'{shrink_axis}' cannot shrink below its current size {size}"
            + (f"; survivors cannot re-tile the non-batch axes "
               f"{non_batch} at a smaller extent" if non_batch else ""),
            axes=old_axes, dead_ranks=dead_ranks, batch_axis=shrink_axis)
    devices = np.take(mesh.devices, slots[:new_size], axis=axis)
    return Mesh(devices, tuple(names))


class PodTopology:
    """The pod's host failure domains: which devices belong to which host.

    A "host" is the unit that fails together — one process of a real
    multi-host job (``jax.distributed``), or one virtual group of
    ``devices_per_host`` consecutive devices in the single-process
    simulated pod CI runs on (``MXNET_TPU_POD_HOSTS`` virtual hosts over
    the forced CPU devices). Everything host-domain-aware — the
    host-slice mesh shrink, the distributed checkpoint commit, the
    watchdog's pod liveness — consumes this one mapping, so the two
    modes exercise the same code paths.

    ``devices`` is the HOST-MAJOR device order the pod mesh is built
    over: host h owns the contiguous flat ordinals
    ``[h*devices_per_host, (h+1)*devices_per_host)``.
    """

    def __init__(self, num_hosts, devices_per_host, this_host=0,
                 devices=None, simulated=True):
        self.num_hosts = int(num_hosts)
        self.devices_per_host = int(devices_per_host)
        self.this_host = int(this_host)
        self.simulated = bool(simulated)
        self.devices = list(devices) if devices is not None else None
        if self.num_hosts <= 0 or self.devices_per_host <= 0:
            raise ValueError(
                f"pod needs positive host/device counts, got "
                f"{num_hosts} hosts x {devices_per_host} devices")
        if not 0 <= self.this_host < self.num_hosts:
            raise ValueError(
                f"this_host={this_host} out of range for "
                f"{num_hosts}-host pod")

    @classmethod
    def detect(cls, devices=None):
        """The running job's topology: real multi-process (one host per
        jax process) when ``jax.process_count() > 1``; otherwise a
        simulated pod over the local devices with ``MXNET_TPU_POD_HOSTS``
        virtual hosts (default 1 — a single-host "pod")."""
        import jax

        if devices is None:
            devices = jax.devices()
        devices = sorted(devices, key=lambda d: (d.process_index, d.id))
        if jax.process_count() > 1:
            per = {}
            for d in devices:
                per.setdefault(d.process_index, []).append(d)
            counts = {len(v) for v in per.values()}
            if len(counts) != 1:
                raise ValueError(
                    f"uneven pod: per-host device counts {sorted(counts)}")
            return cls(len(per), counts.pop(),
                       this_host=jax.process_index(), devices=devices,
                       simulated=False)
        hosts = int(os.environ.get("MXNET_TPU_POD_HOSTS", "1"))
        return cls.simulated(hosts, devices)

    @classmethod
    def simulated(cls, num_hosts, devices=None):
        """Partition the local devices into ``num_hosts`` virtual hosts
        of equal size (the CI pod: N virtual hosts x M forced CPU
        devices in one process)."""
        import jax

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        num_hosts = int(num_hosts)
        if num_hosts <= 0 or len(devices) % num_hosts:
            raise ValueError(
                f"{len(devices)} devices do not split into {num_hosts} "
                "equal virtual hosts")
        return cls(num_hosts, len(devices) // num_hosts, this_host=0,
                   devices=devices, simulated=True)

    @property
    def total_devices(self):
        return self.num_hosts * self.devices_per_host

    def host_of(self, ordinal):
        """Host index owning flat (host-major) device ordinal."""
        return int(ordinal) // self.devices_per_host

    def host_ordinals(self, host):
        """The flat device ordinals host ``host`` owns."""
        host = int(host)
        if not 0 <= host < self.num_hosts:
            raise ValueError(f"host {host} out of range for "
                             f"{self.num_hosts}-host pod")
        start = host * self.devices_per_host
        return tuple(range(start, start + self.devices_per_host))

    def host_of_device(self, device):
        """Host index owning a jax device (real mode: its process;
        simulated mode: position in the host-major device order)."""
        if not self.simulated:
            return int(device.process_index)
        if self.devices is None:
            raise ValueError("simulated topology built without devices")
        for i, d in enumerate(self.devices):
            if d is device or d.id == device.id:
                return self.host_of(i)
        raise ValueError(f"device {device} is not part of this pod")

    def hosts(self):
        return tuple(range(self.num_hosts))

    def shrunk(self, kept_hosts):
        """The topology after excising every host not in ``kept_hosts``
        (survivor hosts are renumbered 0..k-1 in their original order)."""
        kept = sorted(int(h) for h in kept_hosts)
        if self.this_host in kept:
            new_this = kept.index(self.this_host)
        else:
            new_this = 0  # a dead host's own process never gets here
        devices = None
        if self.devices is not None:
            devices = [self.devices[o] for h in kept
                       for o in self.host_ordinals(h)]
        return PodTopology(len(kept), self.devices_per_host,
                           this_host=new_this, devices=devices,
                           simulated=self.simulated)

    def describe(self):
        return {"num_hosts": self.num_hosts,
                "devices_per_host": self.devices_per_host,
                "this_host": self.this_host,
                "simulated": self.simulated}

    def __repr__(self):
        return (f"PodTopology(hosts={self.num_hosts}, "
                f"devices_per_host={self.devices_per_host}, "
                f"this_host={self.this_host}, "
                f"simulated={self.simulated})")


def pod_mesh(axes=None, topology=None):
    """The global named mesh of a pod, in HOST-MAJOR device order, plus
    its topology: host h's devices occupy the contiguous flat ordinals
    ``[h*M, (h+1)*M)`` of ``mesh.devices`` (C order), so a whole host
    maps onto whole slots of some named axis and ``shrink_mesh_hosts``
    can excise it. Returns ``(mesh, topology)``.

    ``axes`` defaults to pure data parallelism over every device in the
    pod. On a real multi-host job every process builds the SAME global
    mesh (same device order — sorted by (process, id)); in the simulated
    pod the host-major order is simply the local device list.
    """
    if topology is None:
        topology = PodTopology.detect()
    devices = topology.devices
    if devices is None:
        import jax

        devices = sorted(jax.devices(),
                         key=lambda d: (d.process_index, d.id))
        topology.devices = list(devices)
    if axes is None:
        axes = {"dp": len(devices)}
    return create_mesh(axes, devices), topology


def _axis_slot_ordinals(shape, axis):
    """slot -> frozenset of flat (C-order) ordinals in that slot of
    ``axis`` for a mesh of the given shape."""
    ordinals = np.arange(int(np.prod(shape))).reshape(shape)
    moved = np.moveaxis(ordinals, axis, 0)
    return [frozenset(int(o) for o in moved[s].ravel())
            for s in range(shape[axis])]


def shrink_mesh_hosts(mesh, dead_hosts, topology, batch_axis="dp"):
    """Excise entire hosts from a host-major pod mesh: the host-domain
    generalization of :func:`shrink_mesh` (which excises one rank's slot
    along the batch axis). A dead HOST takes all of its devices with it,
    wherever they sit in the mesh — so the shrink axis is chosen as the
    first named axis (batch axis preferred, then mesh order) whose slots
    the dead hosts' device set exactly tiles. The surviving extent on
    that axis is trimmed to the largest power of two (same degrade
    ladder and batch-divisibility contract as ``shrink_mesh``).

    Returns ``(new_mesh, new_topology, kept_hosts)`` where
    ``kept_hosts`` are the ORIGINAL host indices that survived into the
    new mesh (in order) and ``new_topology`` renumbers them 0..k-1.
    Raises a structured :class:`MeshShrinkError` when the dead hosts'
    devices do not align to whole slots of any axis, or no viable
    smaller mesh exists.
    """
    from jax.sharding import Mesh

    names = list(mesh.axis_names)
    shape = tuple(int(s) for s in mesh.devices.shape)
    old_axes = dict(zip(names, shape))
    dead = sorted({int(h) for h in dead_hosts})
    if not dead:
        raise MeshShrinkError("no dead hosts to excise", axes=old_axes,
                              batch_axis=batch_axis)
    bad = [h for h in dead if not 0 <= h < topology.num_hosts]
    if bad:
        raise MeshShrinkError(
            f"dead host(s) {bad} out of range for "
            f"{topology.num_hosts}-host pod", axes=old_axes,
            dead_ranks=dead, batch_axis=batch_axis)
    dead_ordinals = frozenset(
        o for h in dead for o in topology.host_ordinals(h))
    batch_names = ((batch_axis,) if isinstance(batch_axis, str)
                   else tuple(batch_axis))
    order = [n for n in batch_names if n in names] + \
        [n for n in names if n not in batch_names]
    chosen = None
    for name in order:
        axis = names.index(name)
        slot_sets = _axis_slot_ordinals(shape, axis)
        lost = [s for s, members in enumerate(slot_sets)
                if members & dead_ordinals]
        covered = frozenset(o for s in lost for o in slot_sets[s])
        if covered == dead_ordinals and len(lost) < shape[axis]:
            chosen = (name, axis, lost)
            break
    if chosen is None:
        raise MeshShrinkError(
            f"dead host(s) {dead} (device ordinals "
            f"{sorted(dead_ordinals)}) do not align to whole slots of "
            f"any axis of mesh {old_axes}; the pod cannot excise them "
            "without re-tiling the survivors — restart the job on the "
            "remaining hosts instead", axes=old_axes, dead_ranks=dead,
            batch_axis=batch_names[0])
    name, axis, lost_slots = chosen
    slots = [s for s in range(shape[axis]) if s not in lost_slots]
    new_size = 1 << (len(slots).bit_length() - 1)
    if new_size >= shape[axis]:
        raise MeshShrinkError(
            f"'{name}' cannot shrink below its current size "
            f"{shape[axis]}", axes=old_axes, dead_ranks=dead,
            batch_axis=name)
    devices = np.take(mesh.devices, slots[:new_size], axis=axis)
    new_mesh = Mesh(devices, tuple(names))
    # hosts kept = hosts ALL of whose ordinals survive into the new mesh
    # (the power-of-two trim may drop additional live hosts' slots)
    id_to_ordinal = {id(d): i for i, d in enumerate(mesh.devices.flat)}
    kept_ordinals = {id_to_ordinal[id(d)] for d in devices.flat}
    kept_hosts = [h for h in topology.hosts()
                  if set(topology.host_ordinals(h)) <= kept_ordinals]
    if not kept_hosts:
        raise MeshShrinkError(
            f"no whole host survives the '{name}' shrink to {new_size} "
            "slot(s)", axes=old_axes, dead_ranks=dead, batch_axis=name)
    return new_mesh, topology.shrunk(kept_hosts), tuple(kept_hosts)


def default_mesh(n_devices=None):
    import jax

    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return create_mesh({"dp": len(devs)}, devs)


def parse_mesh_spec(spec):
    """Parse a 'dp=2,fsdp=2,tp=-1' mesh-shape string into an ordered
    axis dict (a -1 size absorbs the remaining devices, create_mesh
    semantics). Axis names must come from AXES so a typo'd axis fails
    loudly instead of silently replicating."""
    axes = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad mesh axis {part!r} in {spec!r}: want name=size")
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in AXES:
            raise ValueError(
                f"unknown mesh axis {name!r} in {spec!r}: want one of {AXES}")
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
        axes[name] = int(val)
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


def named_mesh(spec=None, devices=None):
    """The named multi-axis training mesh (docs/parallel.md).

    ``spec`` is a 'dp=2,fsdp=2,tp=2' string, an axis dict, or None to
    read the ``MXNET_TPU_MESH_SHAPE`` env knob; with neither set this
    degrades to the pure data-parallel default_mesh so single-axis
    callers need no configuration. Axes with size 1 are kept — a
    dp=2,fsdp=1,tp=4 mesh still names all three axes so SpecLayout
    rules resolve uniformly.
    """
    if spec is None:
        spec = os.environ.get("MXNET_TPU_MESH_SHAPE", "").strip()
        if not spec:
            return default_mesh() if devices is None else create_mesh(
                {"dp": len(list(devices))}, devices)
    axes = spec if isinstance(spec, dict) else parse_mesh_spec(spec)
    return create_mesh(axes, devices)


def shard_map(fn, mesh, in_specs, out_specs, check=True):
    """``jax.shard_map`` with the varying-mesh-axes check as one flag
    (off around ``pallas_call`` bodies, whose outputs carry no vma)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
