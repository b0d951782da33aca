"""TPU resource allocator: assign chips to service workers.

Reference semantics: deploy/dynamo/sdk cli/allocator.py:35-136 — the
reference pins GPUs per worker via CUDA_VISIBLE_DEVICES; the TPU equivalent
pins chips via TPU runtime env (TPU_VISIBLE_CHIPS / JAX platform selection).
Workers that request no accelerator get JAX_PLATFORMS=cpu so they never
touch (or lock) the TPU runtime — important because a TPU chip is held
exclusively by one process.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def count_chips() -> int:
    """Chips on this host, counted WITHOUT touching JAX: the supervisor
    that allocates must never hold a chip its workers need.  Order:
    ``DYN_TPU_CHIPS``, else the TPU device files (``/dev/accel*`` on v4/v5
    hosts, ``/dev/vfio/<n>`` where the runtime uses vfio)."""
    env = os.environ.get("DYN_TPU_CHIPS", "").strip()
    if env:
        return int(env)
    import glob

    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return sum(
        1 for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()
    )


@dataclass
class Allocation:
    env: Dict[str, str] = field(default_factory=dict)
    chips: List[int] = field(default_factory=list)


class TpuAllocator:
    """Hands out chip sets worker by worker; oversubscription is an error."""

    def __init__(self, total_chips: Optional[int] = None):
        if total_chips is None:
            total_chips = count_chips()
        self.total_chips = total_chips
        self._next = 0

    def assign(self, resources: Dict) -> Allocation:
        want = int(resources.get("tpu", 0) or 0)
        if want == 0:
            return Allocation(env={"JAX_PLATFORMS": "cpu"})
        if self._next + want > self.total_chips:
            raise RuntimeError(
                f"TPU oversubscribed: need {want}, "
                f"{self.total_chips - self._next} of {self.total_chips} left"
            )
        chips = list(range(self._next, self._next + want))
        self._next += want
        return Allocation(
            env={"TPU_VISIBLE_CHIPS": ",".join(map(str, chips)),
                 "TPU_CHIPS_PER_PROCESS_BOUNDS": f"1,1,{want}"},
            chips=chips,
        )
