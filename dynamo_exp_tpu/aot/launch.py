"""How a launch's host arrays reach the device: one buffer, one transfer
(docs/engine_perf.md "How a launch reaches the device").

A ragged dispatch used to hand the device 8-19 host arrays of a few
hundred bytes to a quarter of a megabyte, each a transfer of its own.
They now travel as ONE flat ``int32`` buffer, cut apart again by static
slices in the first lines of the program. :func:`launch_layout` is the
only place that says which fields a program family takes, in which order
and at which offset; the engine's builders write their rows into its
views (:func:`launch_buffer`), both programs unpack by it
(:func:`unpack_launch`), and ``aot/warmup.py``'s padding batch is the
same buffer as it starts, so the four cannot drift. ``float32`` fields
travel as their bits, ``is_decode`` as 0 / 1.

What is not in the buffer lives on the device already and stays a
separate argument: parameters, the KV pools, the penalty counts, a
model's state pool, and a chained window's carry.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np


class LaunchField(NamedTuple):
    name: str
    shape: tuple[int, ...]
    dtype: str  # "int32" | "float32" | "bool"
    offset: int  # in int32 elements

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))


_SAMPLER = (
    ("seeds", "int32"), ("temp", "float32"), ("top_k", "int32"),
    ("top_p", "float32"), ("freq", "float32"), ("pres", "float32"),
    ("rep", "float32"),
)

# ``TPUEngine._row_sampler_args``' arrays, in its order.
SAMPLER_FIELDS = tuple(name for name, _ in _SAMPLER)


def _block_fields(nb, windowed, full_sampler, block, B1, pmax) -> list:
    """A block-diffusion model's launch (``engine/block.py``): a decode
    row is a block of ``block`` positions with what is fixed in it
    (``fixed_at``: ``ops/sampling.py::denoise_select``) and the denoising
    passes it has had. The block program takes a block a row (``tokens``
    [nb, block] from ``positions``, the block's start) and, where ``prev``
    is 1, the block before it, final but not in the pages yet, whose K/V
    the opening pass writes: ``prev_tokens``, or with ``chained`` 1 the
    tokens the dispatch before returned (a device argument). The mixed
    one takes a flat stream in which ``blk_idx`` finds each decode row's
    block (``passes`` -1: not a decode row)."""
    rows = nb if windowed else B1
    if windowed:
        named = [
            ("tokens", (nb, block), "int32"), ("positions", (nb,), "int32"),
            ("prev_tokens", (nb, block), "int32"), ("prev", (nb,), "int32"),
            ("chained", (1,), "int32"),
        ]
    else:
        named = [
            ("tokens", (nb,), "int32"), ("positions", (nb,), "int32"),
            ("row_of", (nb,), "int32"), ("blk_idx", (B1, block), "int32"),
        ]
    named += [
        ("fixed_at", (rows, block), "int32"), ("passes", (rows,), "int32"),
        ("table", (rows, pmax), "int32"),
    ]
    if full_sampler:
        named += [(name, (rows,), dtype) for name, dtype in _SAMPLER]
    return named


@functools.lru_cache(maxsize=None)
def _layout(nb, windowed, full_sampler, with_spec, has_seq_state,
            B1, pmax, S, T_s, block=0) -> tuple[LaunchField, ...]:
    if block:
        return _at_offsets(
            _block_fields(nb, windowed, full_sampler, block, B1, pmax)
        )
    rows = nb if windowed else B1
    if windowed:
        # ``chained``: 1 where tokens and positions are the carry of the
        # window before (a device argument) and the fields here are unset.
        named = [
            ("tokens", (nb,), "int32"), ("positions", (nb,), "int32"),
            ("chained", (1,), "int32"), ("max_pos", (nb,), "int32"),
            ("table", (nb, pmax), "int32"),
        ]
    else:
        named = [
            ("tokens", (nb,), "int32"), ("positions", (nb,), "int32"),
            ("row_of", (nb,), "int32"), ("table", (B1, pmax), "int32"),
            ("q_last", (B1,), "int32"),
        ]
    if full_sampler:
        if not windowed:
            named += [("pos0", (B1,), "int32"), ("is_decode", (B1,), "bool")]
        named.append(("slot_map", (rows,), "int32"))
        named += [(name, (rows,), dtype) for name, dtype in _SAMPLER]
    if windowed:
        named += [
            ("stop_set", (nb, S), "int32"), ("eos_gate", (nb,), "int32"),
            ("budget_gate", (nb,), "int32"),
        ]
    elif with_spec:
        named.append(("spec_idx", (B1, T_s), "int32"))
        if full_sampler:
            named.append(("spec_pos", (B1, T_s), "int32"))
        named += [
            ("spec_drafts", (B1, max(T_s - 1, 1)), "int32"),
            ("n_drafts", (B1,), "int32"),
        ]
    if has_seq_state:
        named.append(("state_slots", (rows,), "int32"))
        if not windowed:
            named += [
                ("state_src", (2 * B1,), "int32"),
                ("state_dst", (2 * B1,), "int32"),
            ]
    return _at_offsets(named)


def _at_offsets(named) -> tuple[LaunchField, ...]:
    fields, offset = [], 0
    for name, shape, dtype in named:
        fields.append(LaunchField(name, shape, dtype, offset))
        offset += fields[-1].size
    return tuple(fields)


def _sizes(cfg) -> tuple:
    # ``table``'s columns: a row's pages, and behind them, where the cache
    # is by kind, its pages of the sliding pool (``split_tables``)
    return (
        cfg.max_decode_slots + 1,
        cfg.max_pages_per_seq * (2 if cfg.model.kv_by_kind else 1),
        cfg.device_stop_width, cfg.spec_max_draft + 1,
        cfg.model.block_length,
    )


def split_tables(cfg, table):
    """A launch's ``table`` field as the paged forwards take it: itself, or
    for a model whose cache is by kind the pair (full, sliding) of its two
    halves (``models/llama.py::init_kv_cache``)."""
    if not cfg.model.kv_by_kind:
        return table
    pmax = cfg.max_pages_per_seq
    return table[:, :pmax], table[:, pmax:]


def launch_layout(cfg, key: tuple) -> tuple[LaunchField, ...]:
    """The ordered fields of one launch of the program ``key`` names
    (``resolve_ragged_key``'s tuple) on an engine configured by ``cfg``:
    a pure function of the key's family (window / mixed, greedy / full
    sampler, ``with_spec``), whether the model keeps sequence state, and
    the static sizes. The page bound and ``want_lp`` change no input."""
    nb, _pages, windowed, full_sampler, _want_lp, with_spec = key
    return _layout(
        nb, windowed, full_sampler, with_spec, cfg.model.has_seq_state,
        *_sizes(cfg),
    )


@functools.lru_cache(maxsize=None)
def _padding(nb, windowed, full_sampler, with_spec, has_seq_state, sizes,
             decode_window, no_entry) -> tuple[tuple, bytes, int]:
    """``launch_buffer``'s plan for one variant: where each field of the
    family lies (name, shape, numpy dtype, byte offset) in a buffer that
    holds the variant's layout first and, behind it, the fields its
    program does not take; that buffer's bytes as the all-padding batch;
    and the layout's length in elements."""
    layout = _layout(nb, windowed, full_sampler, with_spec, has_seq_state, *sizes)
    block = sizes[-1]
    every = _layout(nb, windowed, True, not windowed and not block, True, *sizes)
    taken = {f.name: f.offset for f in layout}
    total = end = layout[-1].offset + layout[-1].size
    plan = []
    for f in every:
        at = taken.get(f.name)
        if at is None:  # scratch, behind the layout
            at, end = end, end + f.size
        dtype = np.float32 if f.dtype == "float32" else np.int32
        plan.append((f.name, f.shape, dtype, 4 * at))
    B = sizes[0] - 1
    fills = {
        "positions": -1, "max_pos": -1, "pos0": -1, "stop_set": -1,
        "spec_pos": -1, "spec_drafts": -1, "top_p": 1.0, "rep": 1.0,
        "budget_gate": decode_window, "slot_map": B, "row_of": B,
        "state_slots": no_entry, "state_dst": no_entry,
        "fixed_at": -1, "passes": -1,
    }
    raw = bytearray(4 * end)
    for name, shape, dtype, at in plan:
        if name in fills:
            np.ndarray(shape, dtype, raw, at).fill(fills[name])
    return tuple(plan), bytes(raw), total


def launch_buffer(
    cfg, key: tuple, no_entry: int = 0, like: np.ndarray | None = None
) -> tuple[np.ndarray, dict]:
    """A fresh buffer for one launch of ``key``'s program, and by name
    every field a builder of its family fills: those the program takes
    as views of the buffer (a ``float32`` field as ``float32``,
    ``is_decode`` as its 0 / 1), the others (a greedy launch's sampler
    rows, a draft-free one's spans) as scratch that goes nowhere, so a
    builder reads the same whatever the variant.

    It starts as the all-padding batch: every row parked at position -1
    so its writes drop, pad rows scattering through the scratch counts
    row and the scratch per-row index, gates that never fire, and for a
    model with sequence state every row at ``no_entry``, an entry past
    the pool (nothing read back, nothing written). A builder writes its
    live rows over that; prewarm and the AOT compiler launch it as it
    is. With ``like``, the buffer an earlier launch of the same variant
    went out with, it starts as that one's fields instead (a chained
    window's rows are the rows of the window before).

    Never a buffer handed out before: the CPU backend may alias a host
    array. One ``bytearray`` copy of cached bytes and one view a field:
    no numpy fill or copy of more than a few hundred elements, each of
    which lets go of the GIL and, under many streams' threads, waits to
    have it back (PERF.md §6, PR 36)."""
    nb, _pages, windowed, full_sampler, _want_lp, with_spec = key
    plan, padding, total = _padding(
        nb, windowed, full_sampler, with_spec, cfg.model.has_seq_state,
        _sizes(cfg), cfg.decode_window, no_entry,
    )
    raw = bytearray(padding)
    if like is not None:
        raw[: 4 * total] = like.data
    fields = {
        name: np.ndarray(shape, dtype, raw, at) for name, shape, dtype, at in plan
    }
    return np.ndarray((total,), np.int32, raw), fields


def unpack_launch(layout: tuple[LaunchField, ...], packed) -> dict:
    """The fields of ``packed`` by name, each with its shape and dtype:
    static slices (and a bit cast), traced into the program's first lines."""
    import jax.numpy as jnp
    from jax import lax

    out = {}
    for f in layout:
        x = lax.slice(packed, (f.offset,), (f.offset + f.size,)).reshape(f.shape)
        if f.dtype == "float32":
            x = lax.bitcast_convert_type(x, jnp.float32)
        elif f.dtype == "bool":
            x = x != 0
        out[f.name] = x
    return out
