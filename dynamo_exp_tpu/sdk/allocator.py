"""TPU chip allocation for multi-service hosts.

Reference parity: ``deploy/dynamo/sdk/cli/allocator.py:53-120``
(``ResourceAllocator.assign_gpus`` → ``CUDA_VISIBLE_DEVICES`` per
watcher). A chip belongs to one process at a time, so every worker
process of a host gets its own chip through libtpu's environment:
``TPU_VISIBLE_CHIPS`` names the chip, and the per-process bounds tell
libtpu the process is a whole one-chip slice of its own (without them
each process still looks for the host's full topology). A service asks
with ``resources={"tpu": n}``; only ``n == 1`` (a replica per chip) is
established on the installed libtpu — a service that wants the whole
host runs alone, unrestricted, with ``--tp``. Services with no tpu
request are told to stay off the accelerator (``JAX_PLATFORMS=cpu``),
so frontends/routers never grab a chip.

Established on libtpu 0.0.34 / one v5litepod-4 host (CHANGES.md PR 21):
``TPU_VISIBLE_CHIPS`` alone lets the first process in and fails the
rest on libtpu's lock file; with both bounds, four processes hold four
chips at once, each seeing its chip as device 0.
"""

from __future__ import annotations

import glob
import os


def host_chips() -> int:
    """Chips this host has: ``$DYN_TPU_CHIPS`` when set, else the TPU
    device nodes (a v5e host exposes one ``/dev/vfio/<n>`` per chip).
    A host with neither has none — say ``--tpu-chips`` to pretend."""
    env = os.environ.get("DYN_TPU_CHIPS", "").strip()
    if env:
        return int(env)
    return len(glob.glob("/dev/vfio/[0-9]*"))


class AllocationError(RuntimeError):
    pass


class TPUAllocator:
    """Free-set allocator: chips released by a scaled-down worker are
    handed to the next scale-up (the planner adds/removes workers at
    runtime, so a bump pointer would leak the budget)."""

    def __init__(self, total_chips: int | None = None):
        if total_chips is None:
            total_chips = host_chips()
        self.total_chips = total_chips
        self._free = set(range(total_chips))

    @property
    def available(self) -> int:
        return len(self._free)

    def assign(self, service_name: str, chips: int) -> dict[str, str]:
        """Env vars for one worker process of ``service_name``."""
        if chips <= 0:
            # Host-side service: keep JAX off the TPU entirely.
            return {"JAX_PLATFORMS": "cpu"}
        if chips != 1:
            raise AllocationError(
                f"{service_name} wants {chips} TPU chips in one worker; "
                "only one chip per worker process is supported here"
            )
        if not self._free:
            raise AllocationError(
                f"{service_name} wants a TPU chip but none of "
                f"{self.total_chips} remain"
            )
        chip = min(self._free)
        self._free.discard(chip)
        return {
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }

    def release(self, env: dict[str, str]) -> None:
        """Return a worker's chip (from its assign() env) to the pool."""
        chip = env.get("TPU_VISIBLE_CHIPS", "")
        if chip:
            self._free.add(int(chip))
