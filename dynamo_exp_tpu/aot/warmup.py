"""Warm-boot provisioning: populate the engine's compiled-variant
caches from the persistent compilation cache *before* first traffic
(docs/aot.md "Warm boot").

``prewarm_engine`` runs on the boot thread, strictly before the engine
loop starts (``TPUEngine.prewarm`` refuses a running engine) — the same
pre-thread window ``__init__`` owns, so no loop-owned state is shared
yet. For every manifest entry it builds the engine's jit wrapper
(populating ``_ragged_fns`` — which also satisfies the engine's
cache-size-delta compile-freshness heuristic) and executes it ONCE with
an all-padding batch:

- every row sits at position -1, so KV writes drop and nothing the
  batch computes can reach an emitted token;
- donated buffers (KV pools, penalty counts) are threaded through and
  reassigned, exactly like a live dispatch;
- with the persistent compilation cache populated (``llmctl aot
  compile``, or a previous boot), the execution's compile step is a
  deserialization — tens of milliseconds instead of tens of seconds —
  and it also loads the program onto the device, so the *second*
  execution (the first real dispatch) is steady-state fast.

The page-move family (gather / scatter / COW) prewarms the same way
(gather page 0, scatter its own content back — an identity write), and
the dispatch profiler's ``first_variant`` freshness state is seeded for
every prewarmed key, so a prewarmed variant's first *traffic* dispatch
is never mis-charged as a cold compile:
``dynamo_compile_cache_misses_total`` stays 0 after a warm boot.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass

import numpy as np

from .launch import launch_buffer
from .lattice import CompileManifest

log = logging.getLogger(__name__)


@dataclass
class PrewarmReport:
    """What one warm boot did (mirrored into ``engine.metrics()`` and
    the ``dynamo_prewarm_*`` telemetry series)."""

    manifest_hash: str = ""
    ragged_variants: int = 0
    move_variants: int = 0
    seconds: float = 0.0

    @property
    def variants(self) -> int:
        return self.ragged_variants + self.move_variants


# ------------------------------------------------------- argument builders
def variant_call_args(engine, key: tuple, packed=None) -> tuple:
    """The full positional argument tuple for one ragged variant's jit
    wrapper: real (sharded, donatable) params / KV pools / penalty
    counts / state pool in their live slots, and the one packed buffer
    of everything else (``launch.launch_buffer``), placed as a live
    launch places it: the all-padding batch, or ``packed``, such a
    buffer whose fields a caller has filled in.

    This is THE shape contract between the AOT compiler, the prewarm
    executor, and the engine's live builders (``_build_windowed`` /
    ``_build_mixed``): the builders write their rows into the same
    buffer, ``compile.py`` lowers with exactly these arguments and
    ``prewarm_engine`` executes with them, so an aval drift from the
    live call sites shows up as a prewarm-then-traffic compile miss the
    smoke gate fails on."""
    nb, _pages, windowed, full_sampler, _want_lp, _with_spec = key
    state = getattr(engine, "seq_state", None)
    if packed is None:
        from ..models.llama import seq_state_entries

        packed, _fields = launch_buffer(
            engine.cfg, key, 0 if state is None else seq_state_entries(state)
        )
    # A compile-only stand-in (benchmark/rehearse/compile_v5e.py) has
    # described devices that hold no array: it lowers from host arrays.
    sharding = getattr(engine, "_launch_sharding", None)
    if sharding is not None:
        import jax

        packed = jax.device_put(packed, sharding)
    args = (engine.params, engine.k_cache, engine.v_cache)
    if windowed:  # no chain: the carry is not read
        # Tokens, then positions; a block model's tokens are a block a row.
        for block in (engine.cfg.model.block_length, 0):
            if sharding is not None:
                args += (engine._no_carry(nb, block),)
            else:
                args += (np.zeros((nb, block) if block else nb, np.int32),)
    args += (packed,)
    if full_sampler:
        args += (engine._counts,)
    return args if state is None else (*args, state)


# ------------------------------------------------------------- execution
def _exec_ragged(engine, key: tuple) -> None:
    """Build + execute one ragged variant as an all-pad batch, threading
    the donated buffers back into the engine exactly like a live
    dispatch consume would."""
    fn = engine._ragged_fn_from_key(key)
    out = fn(*variant_call_args(engine, key))
    if getattr(engine, "seq_state", None) is not None:
        *out, engine.seq_state = out
    _nb, _pages, windowed, full_sampler, _lp, _spec = key
    if windowed and full_sampler:
        _ys, engine.k_cache, engine.v_cache, engine._counts, _t, _p = out
    elif windowed:
        _ys, engine.k_cache, engine.v_cache, _t, _p = out
    elif full_sampler:
        _ys, engine.k_cache, engine.v_cache, engine._counts = out
    else:
        _ys, engine.k_cache, engine.v_cache = out


def _exec_moves(engine, buckets) -> int:
    """Prewarm the page-move family: per bucket, one gather of page 0
    and one scatter writing page 0's own content back (duplicate
    indices, identical updates — a deterministic identity), plus the
    single COW variant (src == dst identity copy)."""
    import jax.numpy as jnp

    n = 0
    for bucket in buckets:
        pids = np.zeros(bucket, np.int32)
        k_b, v_b = engine._gather_pages(
            engine.k_cache, engine.v_cache, jnp.asarray(pids)
        )
        engine.k_cache, engine.v_cache = engine._inject_pages(
            engine.k_cache, engine.v_cache, jnp.asarray(pids), k_b, v_b
        )
        n += 2
    engine.k_cache, engine.v_cache = engine._cow_pages(
        engine.k_cache,
        engine.v_cache,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    if getattr(engine, "seq_state", None) is not None:
        # A prefix hit's state copy (entry 0 onto itself).
        engine.seq_state = engine._copy_state(
            engine.seq_state, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)
        )
        n += 1
    return n + 1


def _seed_profiler(engine, manifest: CompileManifest) -> None:
    """Seed the dispatch profiler's freshness state for every prewarmed
    variant: the ``first_variant`` heuristic predates prewarm and would
    otherwise charge a prewarmed kernel's first *traffic* dispatch as a
    cold compile miss. (The ragged cache needs no seeding — its
    freshness is the ``_ragged_fns`` size delta, and prewarm populated
    the cache.)"""
    prof = engine.profiler
    if prof is None:
        return
    prof.seed_variants("gather", manifest.move_buckets)
    prof.seed_variants("scatter", manifest.move_buckets)
    prof.seed_variants("cow", (0,))


def prewarm_engine(
    engine, manifest: CompileManifest | None = None
) -> PrewarmReport:
    """``TPUEngine.prewarm``'s implementation: compile/load every
    manifest variant before first traffic. Returns the report the
    engine mirrors into metrics/telemetry; ``manifest`` defaults to the
    engine's own full lattice."""
    import jax

    from .compile import manifest_for_engine

    if manifest is None:
        manifest = manifest_for_engine(engine)
    t0 = time.monotonic()  # dynlint: determinism(prewarm wall-clock metric)
    report = PrewarmReport(manifest_hash=manifest.hash())
    for variant in manifest.ragged:
        _exec_ragged(engine, variant.key)
        report.ragged_variants += 1
    report.move_variants = _exec_moves(engine, manifest.move_buckets)
    # Penalty-row init (the first-token path's one extra compiled fn):
    # run it against the scratch row, then zero the residue so the
    # scratch row a cold engine pads with stays all-zero here too.
    engine._counts = engine._init_row(
        engine._counts, engine.cfg.max_decode_slots, 0
    )
    engine._counts = engine._counts.at[engine.cfg.max_decode_slots].set(0)
    # One sync closes the whole prewarm: every executable is compiled,
    # loaded, and executed before the engine reports itself warm.
    jax.block_until_ready((engine.k_cache, engine.v_cache, engine._counts))
    _seed_profiler(engine, manifest)
    report.seconds = time.monotonic() - t0  # dynlint: determinism(prewarm wall-clock metric)
    log.info(
        "prewarm: %d ragged + %d move variants in %.2fs (manifest %s)",
        report.ragged_variants, report.move_variants, report.seconds,
        report.manifest_hash[:12],
    )
    return report
