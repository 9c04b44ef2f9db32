"""Default resource requests for unionml_tpu stages.

Reference parity: ``unionml/defaults.py:5`` pins ``Resources(cpu="1", mem="1Gi")`` from
flytekit. The rebuild defines its own ``Resources`` spec that is TPU-first: stages may
request a TPU pod-slice (accelerator type + topology) instead of GPUs — this is the
"no GPU in the task spec" north-star requirement (BASELINE.json).
"""

from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class Resources:
    """Resource request attached to a stage / job spec.

    ``accelerator`` uses TPU accelerator-type strings (e.g. ``"v5litepod-8"``) as used by
    TPU VM / GKE node-pool provisioning; ``topology`` is the chip topology (e.g. ``"2x4"``).
    ``host_count`` > 1 indicates a multi-host slice requiring ``jax.distributed`` init.
    """

    cpu: str = "1"
    mem: str = "1Gi"
    accelerator: Optional[str] = None
    topology: Optional[str] = None
    host_count: int = 1

    @property
    def device_count(self) -> int:
        """Number of chips implied by ``topology`` (e.g. "2x4" -> 8); 0 when no accelerator."""
        if self.accelerator is None:
            return 0
        if self.topology is None:
            return 1
        count = 1
        for dim in self.topology.lower().split("x"):
            count *= int(dim)
        return count

    def mesh_axes(self) -> Tuple[int, ...]:
        """Topology dims as a tuple usable to build a device mesh."""
        if self.topology is None:
            return (max(self.device_count, 1),)
        return tuple(int(dim) for dim in self.topology.lower().split("x"))


DEFAULT_RESOURCES = Resources(cpu="1", mem="1Gi")

#: Single-host v5e-8 slice — the baseline data-parallel target.
TPU_V5E_8 = Resources(cpu="8", mem="16Gi", accelerator="v5litepod-8", topology="2x4", host_count=1)

#: Single v5e chip — serving target.
TPU_V5E_1 = Resources(cpu="4", mem="8Gi", accelerator="v5litepod-1", topology="1x1", host_count=1)
