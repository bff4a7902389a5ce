"""What Mamba-2's recurrence has to do (``dynamo_exp_tpu/ops/ssd.py``), and
what a chip needs at the least to do it.

The recurrence runs in the chunk kernel ``ssd_chunk`` over a mixed
dispatch's stream and in the in-place kernel ``ssd_step`` a row of one
token, with what XLA puts round them (the chunk tables, the slices of
``x | B | C``, the packed tail's selects), all under the ``jax.named_scope``
``ssd_scan`` of ``models/llama.py``. So the time is the scope's, and the
work comes from what the program counts
(``dynamo_mamba2_tokens_total``: the live slots of every dispatch, each of
which walks every Mamba-2 layer).

OPERATIONS AND BYTES of one token and layer: THE RECURRENCE'S, whatever
implements it, so that the share does not move when the chunk does:

- operations: a head's ``[S, P]`` state is decayed (``S P``), takes the
  rank-one update ``dt B x^T`` (``2 S P``) and is read out against ``C``
  (``2 S P``): ``5 * S * H * P`` a token. The chunked form spends more (a
  group's ``[C, C]`` product ``C B^T``, a head's masked ``[C, C] x [C, P]``
  at half a product's columns, and the ``[S, C] x [C, P]`` of the state at
  full float32 precision, six passes) and does them on the MXU, so the
  operation bound is the bf16 peak's;
- bytes: ``x`` and ``y`` [H * P], ``B`` and ``C`` [G * S] and ``dt`` [H],
  float32, once each. The states (2 MiB a row and layer, read once and
  written at most twice a dispatch; once each way a decode step) are not
  counted: the share reads low, never high.
"""

from __future__ import annotations

import re

from lib import steady_window_time, xplane_meta

_CONTROL = re.compile(r"^(while|conditional|call|tuple|get-tuple-element|parameter|bitcast)$")
SCOPE = re.compile(r"(^|/)ssd_scan(/|$)")
KERNELS = re.compile(r"^%?ssd_(step|chunk)")  # their results hold the pool, aliased


def widths(config: dict) -> tuple[int, int, int, int]:
    """(heads, channels a head, groups, states) of a configuration's file."""
    return (
        int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
        int(config["n_groups"]), int(config["ssm_state_size"]),
    )


def mamba2_layers(config: dict) -> int:
    return str(config["hybrid_override_pattern"]).count("M")


def token_operations(heads: int, head_dim: int, states: int) -> float:
    return 5 * states * heads * head_dim


def token_bytes(heads: int, head_dim: int, groups: int, states: int) -> float:
    return 4 * (2 * heads * head_dim + 2 * groups * states + heads)


def least_seconds(token_layers: float, config: dict, peak: dict) -> tuple[float, str]:
    """The least time ``token_layers`` (tokens x Mamba-2 layers) of the
    recurrence can take on a chip with ``peak`` (an entry of ``peaks.json``),
    and which bound sets it."""
    h, p, g, s = widths(config)
    by_hbm = token_layers * token_bytes(h, p, g, s) / (peak["hbm_gbps"] * 1e9)
    by_mxu = token_layers * token_operations(h, p, s) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")


def scope_seconds(ctx: dict) -> float | None:
    """Device seconds under the scope ``ssd_scan`` over the whole window, on
    ``lib/steady_window_time.py``'s base (the traced slice's share of busy
    time, its long idle gaps taken out of its length, times the window the
    loop's phase counter gives). None without a trace, without such an
    operation or without the counter."""
    from lib.stats import counter_delta

    trace = ctx.get("trace") or {}
    meta = xplane_meta.current() if trace else None
    if not meta or not trace.get("window_s"):
        return None

    def kind(op):
        hit = SCOPE.search(op.scope or "") and not _CONTROL.match(op.opcode or "")
        return "hit" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    busy, hit = sum(by.values()), by.get("hit", 0)
    window_s = counter_delta(
        ctx.get("prom_before") or {}, ctx.get("prom_after") or {},
        "dynamo_engine_phase_seconds_total", {},
    )
    steady = trace["window_s"] - sum(
        seconds for _host, seconds in trace.get("idle_gaps") or ()
        if seconds >= steady_window_time.LONG_GAP_S
    )
    if busy <= 0 or hit <= 0 or window_s <= 0 or steady <= 0:
        return None
    return hit / busy * trace["busy_s"] / steady * window_s
