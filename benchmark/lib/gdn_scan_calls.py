"""The gated-delta-rule kernel's calls in a device trace
(``dynamo_exp_tpu/ops/gated_delta.py``; its custom call is named
``gated_delta_chunk``), with their own shapes, and what a chip needs at
the least to run one.

A call's result is ``(o f32[N, H * dv], the states' pool f32[L, entries,
dk, H * dv])``: ``N`` stream slots, ``H * dv`` value lanes and ``dk``, all
three read from the result (``lib/xplane_meta.py`` keeps an operation's
result type). ``H`` is not in it and comes from the configuration.

OPERATIONS AND BYTES of one call: THE RECURRENCE'S, whatever implements
it, so that the share does not move when the kernel's chunk does:

- operations: a token and head reads ``S^T k`` (``2 dk dv``), adds the
  rank-one update (``2 dk dv``) and reads ``S^T q`` (``2 dk dv``): ``6 *
  dk * dv * H`` a token. The chunked form spends more (the chunk's
  ``[C, C]`` products and a triangular solve beside ``4 C dk dv`` for
  the state's two) and does them on the MXU, so the operation bound is
  the bf16 peak's;
- bytes: ``q`` and ``k`` [N, H * dk], ``v`` and ``o`` [N, H * dv], ``g``
  and ``beta`` [N, H], float32, once each. The states of the rows present
  (read once and written at most twice a row, ``dk * H * dv * 4`` bytes
  each) are not in the trace and are left out: the share reads low, never
  high.

A call is counted at ``live`` of its slots, the share of a mixed
dispatch's slots that held a token (the caller reads it from the program's
counters): the kernel walks no padding.
"""

from __future__ import annotations

import functools
import os
import re

from lib import trace_reduce, xplane_meta

KERNEL = re.compile(r"^%?gated_delta_chunk")


def chunk_calls(path: str) -> list[dict]:
    """``_chunk_calls`` of the file as it is now, read once however many
    readers ask."""
    return _chunk_calls(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _chunk_calls(path: str, _mtime: float) -> list[dict]:
    """One entry a kind of ``gated_delta_chunk`` call in the trace:
    ``slots``, ``values`` (H * dv), ``dk``, how many ran (``count``) and
    their summed duration (``ns``; a custom call has no children), over
    all chips."""
    space = xplane_meta._schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    kinds: dict[str, dict | None] = {}
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
                if name not in kinds:
                    kinds[name] = call_of(name)
                kind = kinds[name]
                if kind is not None:
                    kind["count"] += 1
                    kind["ns"] += ev.duration_ps // 1000
    return [k for k in kinds.values() if k is not None]


def call_of(hlo_line: str) -> dict | None:
    """The call's sizes from its HLO line, or None where the result is
    not (o [N, H * dv], pool [L, entries, dk, H * dv])."""
    result, _opcode = xplane_meta.split_hlo(hlo_line)
    arrays = xplane_meta.result_arrays(result)
    flat = [dims for _dt, dims in arrays if len(dims) == 2]
    pools = [dims for _dt, dims in arrays if len(dims) == 4]
    if not flat or not pools or flat[0][1] != pools[0][3]:
        return None
    return {
        "slots": flat[0][0], "values": flat[0][1], "dk": pools[0][2], "count": 0, "ns": 0,
    }


def call_operations(call: dict, live: float = 1.0) -> float:
    return 6 * call["dk"] * call["values"] * call["slots"] * live


def call_bytes(call: dict, heads: int, live: float = 1.0) -> float:
    a_token = 2 * heads * call["dk"] + 2 * call["values"] + 2 * heads
    return 4 * a_token * call["slots"] * live


def least_seconds(call: dict, heads: int, peak: dict, live: float = 1.0) -> tuple[float, str]:
    """The least time one such call can take on a chip with ``peak`` (an
    entry of ``peaks.json``), and which bound sets it: the larger of its
    bytes over the HBM peak and its operations over the bf16 peak."""
    by_hbm = call_bytes(call, heads, live) / (peak["hbm_gbps"] * 1e9)
    by_mxu = call_operations(call, live) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
