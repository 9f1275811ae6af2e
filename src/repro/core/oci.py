"""OCI-style declarative attachment (paper §III.C).

"recent additions to the OCI runtime specification allow for the
declarative attachment of network interfaces. This allows network drivers
to simply instruct the container runtime to move a prepared interface
into the pod's namespace, offloading the privileged, low-level netlink
operations to the runtime itself."

Adapted: drivers never touch global JAX device state (the privileged
operation in this world). They emit an :class:`AttachmentSpec`; the
single trusted :class:`MeshRuntime` executes it — building the
``jax.sharding.Mesh`` and binding device coordinates. This keeps every
driver unprivileged and composable, exactly the paper's intent.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["DeviceBinding", "AttachmentSpec", "MeshRuntime"]


@dataclass(frozen=True)
class DeviceBinding:
    """One declarative binding: physical device -> logical mesh coordinate."""

    device_id: str               # fabric/resource device id (e.g. pod0/chip3_7)
    mesh_coord: Tuple[int, ...]  # logical coordinate in the mesh
    attrs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class AttachmentSpec:
    """The declarative request a driver hands to the runtime.

    Mirrors OCI runtime-spec PR #1271's netdev list: a *description* of
    the desired end state, not a procedure.
    """

    axis_names: Tuple[str, ...]
    axis_shape: Tuple[int, ...]
    bindings: List[DeviceBinding] = field(default_factory=list)
    metadata: Dict[str, Any] = field(default_factory=dict)

    def validate(self) -> None:
        import math
        want = math.prod(self.axis_shape)
        if len(self.bindings) != want:
            raise ValueError(
                f"attachment has {len(self.bindings)} bindings for a "
                f"{self.axis_shape} mesh ({want} coords)")
        coords = {b.mesh_coord for b in self.bindings}
        if len(coords) != want:
            raise ValueError("duplicate/missing mesh coordinates in bindings")
        for b in self.bindings:
            if len(b.mesh_coord) != len(self.axis_shape):
                raise ValueError(f"coord rank mismatch: {b.mesh_coord}")
            for c, s in zip(b.mesh_coord, self.axis_shape):
                if not (0 <= c < s):
                    raise ValueError(f"coord {b.mesh_coord} outside {self.axis_shape}")


class MeshRuntime:
    """The privileged runtime executing attachments (OCI analogue).

    Only this class calls ``jax.devices()`` / constructs meshes. Drivers
    and planners stay declarative.
    """

    def __init__(self) -> None:
        self._executed: List[AttachmentSpec] = []

    def execute(self, spec: AttachmentSpec, jax_devices: Optional[Sequence[Any]] = None):
        """Build a ``jax.sharding.Mesh`` realizing the attachment.

        Physical device ids are mapped onto the process's JAX devices in
        binding order (on real hardware the runtime would match chip
        coordinates; on the CPU dry-run platform the stand-in devices are
        positionally bound — the *placement physics* live in the plan's
        dilation metadata, not in XLA's view).
        """
        import jax

        spec.validate()
        devs = list(jax_devices) if jax_devices is not None else list(jax.devices())
        n = len(spec.bindings)
        if len(devs) < n:
            raise ValueError(f"need {n} JAX devices, have {len(devs)}")
        arr = np.empty(spec.axis_shape, dtype=object)
        # deterministic: bindings sorted by mesh coordinate get devices in order
        for dev, b in zip(devs, sorted(spec.bindings, key=lambda b: b.mesh_coord)):
            arr[b.mesh_coord] = dev
        mesh = jax.sharding.Mesh(
            arr, spec.axis_names,
            axis_types=(jax.sharding.AxisType.Auto,) * len(spec.axis_names))
        self._executed.append(spec)
        return mesh

    @property
    def executed(self) -> Sequence[AttachmentSpec]:
        return tuple(self._executed)
