"""The selective-scan kernel's calls in a device trace
(``dynamo_exp_tpu/ops/selective_scan.py``; its custom call is named
``ssm_scan``), with their own shapes, and what a chip needs at the least
to run one.

A call's result is ``(y f32[N, E], the scan's pool f32[L, entries, S,
E])``: ``N`` stream slots, ``E`` channels, ``S`` states a channel, all
three read from the result (``lib/xplane_meta.py`` keeps an operation's
result type).

OPERATIONS AND BYTES of one call, what the recurrence needs and not what
this kernel happens to read:

- operations: for every slot, channel and state one ``exp`` and six
  multiply-adds (``dt*A``, ``dA*h``, ``dt*x``, ``.*B``, ``+``, ``h*C``
  with its sum): ``7 * N * E * S``. On the vector unit; ``peaks.json``
  has no peak for it, so no bound is set from them;
- bytes: ``x``, ``dt`` and ``y`` [N, E] float32 once each, ``B`` and ``C``
  [N, S] float32 (the kernel reads them broadcast over 128 lanes, which
  the recurrence does not need), ``A`` [S, E] and ``D`` [E]. The states
  of the rows present (read once and written at most twice a row, ``S *
  E * 4`` bytes each) are not in the trace and are left out: the count
  is too low by at most 3 % at 8 rows a 4096-slot call, and the share
  reads low, never high.
"""

from __future__ import annotations

import functools
import os
import re

from lib import trace_reduce, xplane_meta

KERNEL = re.compile(r"^%?ssm_scan")


def scan_calls(path: str) -> list[dict]:
    """``_scan_calls`` of the file as it is now, read once however many
    readers ask."""
    return _scan_calls(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _scan_calls(path: str, _mtime: float) -> list[dict]:
    """One entry a kind of ``ssm_scan`` call in the trace: ``slots``,
    ``channels``, ``states``, how many ran (``count``) and their summed
    duration (``ns``; a custom call has no children), over all chips."""
    space = xplane_meta._schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    kinds: dict[str, dict] = {}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        metas = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                name = metas[ev.metadata_id].name
                if not KERNEL.match(name):
                    continue
                kind = kinds.get(name)
                if kind is None:
                    kind = kinds[name] = call_of(name)
                    if kind is None:
                        del kinds[name]
                        continue
                kind["count"] += 1
                kind["ns"] += ev.duration_ps // 1000
    return list(kinds.values())


def call_of(hlo_line: str) -> dict | None:
    """The call's sizes from its HLO line, or None where the result is
    not (y [N, E], pool [L, entries, S, E])."""
    result, _opcode = xplane_meta.split_hlo(hlo_line)
    arrays = xplane_meta.result_arrays(result)
    flat = [dims for _dt, dims in arrays if len(dims) == 2]
    pools = [dims for _dt, dims in arrays if len(dims) == 4]
    if not flat or not pools or flat[0][1] != pools[0][3]:
        return None
    return {
        "slots": flat[0][0], "channels": flat[0][1], "states": pools[0][2],
        "count": 0, "ns": 0,
    }


def call_operations(call: dict) -> int:
    return 7 * call["slots"] * call["channels"] * call["states"]


def call_bytes(call: dict) -> int:
    n, e, s = call["slots"], call["channels"], call["states"]
    return 4 * (3 * n * e + 2 * n * s + s * e + e)


def least_seconds(call: dict, peak: dict) -> tuple[float, str]:
    """The least time one such call can take on a chip with ``peak`` (an
    entry of ``peaks.json``), and which bound sets it: HBM's, the only
    peak the table has for a kernel that multiplies no matrices."""
    return call_bytes(call) / (peak["hbm_gbps"] * 1e9), "hbm"
