"""The routed experts' matrix products in a device trace, with their own
shapes, and what a chip needs at the least to run one.

XLA:TPU lowers ``jax.lax.ragged_dot`` (``ops/moe.py::moe_ffn``: three a
layer) to a custom call the trace names ``ragged-dot-none.<n>`` and by
its whole HLO line::

    %ragged-dot-none.1 = bf16[128,3584]{..} custom-call(bf16[128,4096]{..} %x,
        bf16[8,4096,3584]{..} %w, s32[8]{..} %group_sizes), custom_call_target=...

``lib/xplane_meta.py`` keeps an operation's result type only, so the
operands are read here, from the same file with the same schema. The
three-dimensional operand is one layer's expert matrix as it lies on a
chip (experts, rows in, this chip's columns out): the shape by which
``readers/trace_expert_weight_copy_share.py`` knows a copy of it.

OPERATIONS AND BYTES of one call, from its shapes (``lhs`` [M, K], ``rhs``
[E, K, N], result [M, N]; M = routed rows, every row in one expert's
group):

- operations: ``2 * M * K * N`` (each row meets one expert's [K, N]);
- bytes: ``lhs`` and the result once, and of ``rhs`` the matrices of the
  experts that can have a row: ``min(E, M)`` of them (the group sizes are
  not in the trace; with hundreds of rows over 8 experts every expert has
  some, and where fewer had, this counts too many bytes and the share
  reads high: a reading over 100 % is a fault of this count).
"""

from __future__ import annotations

import functools
import os
import re

from lib import trace_reduce, xplane_meta

RAGGED_DOT = re.compile(r"^%?ragged-dot")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "s32": 4}


def operand_arrays(hlo_line: str) -> list[tuple[str, tuple[int, ...]]]:
    """The arrays among a call's operands: what stands between the
    opcode's brackets, layouts dropped."""
    _, _, rest = hlo_line.partition(" = ")
    start = rest.find("(", rest.find("custom-call") if "custom-call" in rest else 0)
    depth, end = 0, len(rest)
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return xplane_meta.result_arrays(xplane_meta._LAYOUT.sub("", rest[start + 1 : end]))


def ragged_dot_calls(path: str) -> list[dict]:
    """``_ragged_dot_calls`` of the file as it is now, read once however
    many readers ask."""
    return _ragged_dot_calls(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _ragged_dot_calls(path: str, _mtime: float) -> list[dict]:
    """One entry a kind of ``ragged-dot`` call in the trace: ``lhs``,
    ``rhs``, ``result`` as (dtype, dims), how many ran (``count``) and
    their summed duration (``ns``; a custom call has no children, so its
    duration is its self time), over all chips."""
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
                if not RAGGED_DOT.match(name):
                    continue
                kind = kinds.get(name)
                if kind is None:
                    arrays = operand_arrays(name)
                    result, _opcode = xplane_meta.split_hlo(name)
                    wide = [a for a in arrays if len(a[1]) == 3]
                    flat = [a for a in arrays if len(a[1]) == 2]
                    if not wide or not flat:
                        continue
                    kind = kinds[name] = {
                        "lhs": flat[0], "rhs": wide[0],
                        "result": xplane_meta.result_arrays(result)[0],
                        "count": 0, "ns": 0,
                    }
                kind["count"] += 1
                kind["ns"] += ev.duration_ps // 1000
    return list(kinds.values())


def call_operations(call: dict) -> int:
    (m, k), (_e, _k, n) = call["lhs"][1], call["rhs"][1]
    return 2 * m * k * n


def call_bytes(call: dict) -> int:
    (ldt, (m, k)), (rdt, (e, _k, n)), (odt, _) = call["lhs"], call["rhs"], call["result"]
    return (
        m * k * _ITEMSIZE[ldt] + min(e, m) * k * n * _ITEMSIZE[rdt] + m * n * _ITEMSIZE[odt]
    )


def least_seconds(call: dict, peak: dict) -> tuple[float, str]:
    """The least time one such call can take on a chip with ``peak``
    (an entry of ``peaks.json``), and which bound sets it."""
    by_bytes = call_bytes(call) / (peak["hbm_gbps"] * 1e9)
    by_ops = call_operations(call) / (peak["bf16_tflops"] * 1e12)
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "mxu")
