"""Per-dispatch device profiling (docs/observability.md "Per-dispatch
device profiling").

The engine loop issues three kinds of device dispatch — ragged compute
batches (pure-decode windows and mixed prefill+decode+spec batches
alike, docs/engine_perf.md "One ragged dispatch"), KV page
gather/scatter moves, and eviction offload batches — and in the
overlapped steady state (docs/engine_perf.md) a throughput problem is
always one of two things: the device spent too long *in flight*, or
the host left a *gap* between consuming one dispatch and issuing the
next. This profiler attributes wall time to exactly those two buckets
per dispatch kind, plus compiled-variant cache behavior, using nothing
but ``time.monotonic()`` timestamps taken at call sites the engine
already owns:

- ``begin(kind)`` immediately before the dispatch call records the
  **host gap** since the kind's previous consume (or previous dispatch,
  for kinds that are never host-synced) and returns the timestamp;
- ``end(kind, t0, fresh)`` right after the dispatch call returns stamps
  dispatch completion (and, for a fresh compiled variant, attributes
  the call's wall time — trace + compile + program load — to
  ``dynamo_compile_seconds{kind}``);
- ``consume(kind, t_dispatch)`` right after the *already-present* host
  sync (the ``np.asarray`` the engine was going to do anyway) records
  the **in-flight** time.

Nothing here blocks, syncs, or touches the device: the overhead
guarantee is *zero additional host syncs per dispatch* (asserted by the
sync-spy smoke test in tests/test_dispatch_profile.py), and the overlap
semantics the recompile-guard / chained-decode identity suites police
are untouched. In the chained steady state the host gap collapses
toward zero — which is precisely the signal: a growing gap under
overlap means the host loop, not the device, is the bottleneck (APEX,
arxiv 2506.03296).

Samples live in bounded per-kind reservoirs (recent-window deques), so
``summary()`` percentiles are cheap and memory is O(1); lifetime totals
ride the prometheus histograms.

**Engine-loop phases** (docs/observability.md "Engine phases and the
device trace"). The same object says what the loop thread was doing
between those stamps: ``phase(name)`` brackets one stretch of the loop
body, adds its host time to ``dynamo_engine_phase_seconds_total{phase}``
and opens a ``jax.profiler.TraceAnnotation("dyn/<name>")`` — about a
microsecond and nothing written while no profiler session is on; while
one is, an event on the profiler's own clock in the loop thread's line
of the host plane, beside the device's ``XLA Ops``. The ``launch`` phase
is the interval ``begin``/``end`` already bracket at a ragged dispatch,
so it costs no clock read of its own. Phases do not nest.
"""

from __future__ import annotations

import time
from collections import deque

from .slo import percentile

# The engine's three device-dispatch kinds. Stable, closed set: the
# prometheus label space and the metrics() mirror key on these names. ``ragged`` covers every compute
# dispatch (the pre-ragged engine split it into prefill / decode /
# spec_verify; sim/fit.py still reads those names from old span and
# bench files for back-compat).
DISPATCH_KINDS = ("ragged", "kv_move", "offload")

# Which compiled program a ragged dispatch ran: a pure-decode ``window``
# (``decode_window`` steps on the device) or a ``mixed`` batch (prefill
# chunks, single decode steps, spec spans). The ``program`` label of the
# dispatch histograms; the other kinds carry ``-``.
PROGRAMS = ("window", "mixed")
NO_PROGRAM = "-"

# The engine loop's phases. Closed set: the ``phase`` label space and
# the ``dyn/<phase>`` names a trace reader cuts idle gaps along.
#   intake    submissions drained, cancellations polled, dead waiters reaped
#   kv_admin  leases/pins/reclaims, ledger audit, offload, preemption,
#             prefetch apply/plan, swap-in, remote inject, eviction flush
#   admit     the admit_next loop
#   assemble  the host arrays of one dispatch (and the choice of program)
#   launch    the jitted call with its host-to-device transfers
#   sync      np.asarray of a dispatch's outputs: the host waits here
#   consume   emission, check_stop, page registration, finishes
#   publish   gauge mirroring
#   park      waiting on the wake event: no work, or everything stalled
PHASES = (
    "intake", "kv_admin", "admit", "assemble", "launch", "sync", "consume",
    "publish", "park",
)

# Summary stat fields (also the bench JSON / docs contract).
SUMMARY_FIELDS = (
    "count",
    "host_gap_p50_s",
    "host_gap_p99_s",
    "in_flight_p50_s",
    "in_flight_p99_s",
    "compile_misses",
    "compile_total_s",
)


class _Phase:
    """One stretch of the loop body under a phase name (see
    :meth:`DispatchProfiler.phase`). One object a phase, reused: the
    loop is single-threaded and phases do not nest."""

    __slots__ = ("_prof", "_name", "_label", "_ann", "_t0")

    def __init__(self, prof: "DispatchProfiler", name: str):
        self._prof, self._name, self._label = prof, name, f"dyn/{name}"
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        self._ann = self._prof._annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dt = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self._prof._add_phase(self._name, dt)
        return False


class DispatchProfiler:
    """Host-side per-dispatch timing. All methods are cheap (two clock
    reads and a deque append at worst) and rely on the GIL for the
    cross-thread case (the offload consume arrives from the CopyStream
    thread; ``summary()`` may be called from any thread)."""

    def __init__(self, telemetry=None, reservoir: int = 1024):
        self._tel = telemetry
        self._gap = {k: deque(maxlen=reservoir) for k in DISPATCH_KINDS}
        self._flight = {k: deque(maxlen=reservoir) for k in DISPATCH_KINDS}
        self._count = dict.fromkeys(DISPATCH_KINDS, 0)
        self._compile_misses = dict.fromkeys(DISPATCH_KINDS, 0)
        self._compile_s = dict.fromkeys(DISPATCH_KINDS, 0.0)
        # kind -> (monotonic time, event seq) of the last consume (or
        # dispatch end, for never-synced kinds); cleared on idle so
        # gaps never span a genuinely-idle engine. The event seq gates
        # gap recording: if ANY other profiler event landed in between,
        # the engine was busy with other dispatch kinds and the elapsed
        # time is work inter-arrival, not host overhead — a prefill
        # arriving 5s into a decode-saturated steady state must not
        # read as a 5s prefill host gap.
        self._last_consume: dict[str, tuple[float, int]] = {}
        self._event_seq = 0
        # (family, key) variants already seen — freshness for compiled
        # caches that live inside a single jax.jit (the page-move
        # kernels key variants by bucket shape, invisibly to the
        # engine-level fn caches).
        self._seen_variants: set = set()
        # Imported here, not at the top: the telemetry package is also
        # imported by processes that never load jax (frontends, the sim).
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._phases = {name: _Phase(self, name) for name in PHASES}
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        self._launch_ann = None  # open between begin() and end() of a launch

    # -------------------------------------------------------------- phases
    def phase(self, name: str) -> _Phase:
        """Context manager round one stretch of the engine loop (a name
        from :data:`PHASES`): its host time goes to
        ``dynamo_engine_phase_seconds_total{phase}`` and, while a
        profiler session is on, a ``dyn/<name>`` event goes to the
        trace. Phases do not nest; ``launch`` is not opened here but by
        ``begin``/``end`` at a ragged dispatch."""
        return self._phases[name]

    def _add_phase(self, name: str, seconds: float) -> None:
        self._phase_s[name] += seconds
        if self._tel is not None:
            self._tel.engine_phase_seconds.labels(name).inc(seconds)

    def phase_seconds(self) -> dict[str, float]:
        """Host seconds so far by phase (every phase always present)."""
        return dict(self._phase_s)

    # ------------------------------------------------------------ dispatch
    def begin(self, kind: str, program: str = NO_PROGRAM) -> float:
        """Immediately before the dispatch call; returns its timestamp
        (pass to :meth:`end` and stash for :meth:`consume`). The host
        gap since the kind's previous consume is recorded only when no
        other dispatch activity intervened — back-to-back work of the
        same kind, the case where the elapsed time really is host
        overhead. A ``program`` (ragged dispatches) also opens the
        loop's ``launch`` phase, which :meth:`end` closes on the
        timestamp it takes anyway."""
        if program != NO_PROGRAM:
            self._launch_ann = self._annotation("dyn/launch")
            self._launch_ann.__enter__()
        now = time.monotonic()
        last = self._last_consume.get(kind)
        self._event_seq += 1
        if last is not None and last[1] == self._event_seq - 1:
            gap = max(now - last[0], 0.0)
            self._gap[kind].append(gap)
            if self._tel is not None:
                self._tel.host_gap_seconds.labels(kind, program).observe(gap)
        return now

    def end(self, kind: str, t0: float, fresh: bool = False) -> float:
        """Immediately after the dispatch call returns. ``fresh`` marks
        a compiled-variant cache miss: the call's wall time is the
        first-compile duration (jit traces/compiles synchronously inside
        the call; steady-state calls only enqueue). Returns the
        dispatch-completion timestamp for :meth:`consume`."""
        now = time.monotonic()
        if self._launch_ann is not None:
            self._launch_ann.__exit__(None, None, None)
            self._launch_ann = None
            self._add_phase("launch", max(now - t0, 0.0))
        self._count[kind] += 1
        if fresh:
            dur = max(now - t0, 0.0)
            self._compile_misses[kind] += 1
            self._compile_s[kind] += dur
            if self._tel is not None:
                self._tel.compile_cache_misses.labels(kind).inc()
                self._tel.compile_seconds.labels(kind).observe(dur)
        # Never-synced kinds (scatter moves) get their gap reference
        # here; synced kinds overwrite it with the later consume.
        self._event_seq += 1
        self._last_consume[kind] = (now, self._event_seq)
        return now

    def first_variant(self, family: str, key) -> bool:
        """True exactly once per (family, key): compile-miss detection
        for variant caches the engine can't watch by size (jit-internal
        shape keys)."""
        k = (family, key)
        if k in self._seen_variants:
            return False
        self._seen_variants.add(k)
        return True

    def seed_variants(self, family: str, keys) -> None:
        """Warm-boot seeding (docs/aot.md): mark (family, key) variants
        as already-compiled so their first *traffic* dispatch is never
        charged as a cold compile. The freshness heuristics predate
        prewarm — without this, a prewarmed gather/scatter bucket's
        first live dispatch would read as a miss and break the
        flat-from-first-dispatch guarantee the prewarm-smoke gate
        asserts. (The engine's ragged cache needs no seeding: its
        freshness is a cache-size delta, and prewarm populates the
        cache itself.)"""
        for key in keys:
            self._seen_variants.add((family, key))

    # ------------------------------------------------------------- consume
    def consume(
        self, kind: str, t_dispatch: float, program: str = NO_PROGRAM
    ) -> None:
        """Immediately after the dispatch's existing host sync. Records
        in-flight time and arms the kind's host-gap reference."""
        now = time.monotonic()
        if t_dispatch > 0.0:
            flight = max(now - t_dispatch, 0.0)
            self._flight[kind].append(flight)
            if self._tel is not None:
                self._tel.dispatch_seconds.labels(kind, program).observe(
                    flight
                )
        self._event_seq += 1
        self._last_consume[kind] = (now, self._event_seq)

    def mark_idle(self) -> None:
        """The loop is parking (no work, or everything stalled): drop
        the gap references so wait time never reads as host gap."""
        self._last_consume.clear()

    def compile_total_s(self) -> float:
        """Cumulative fresh-compile seconds across all kinds. The
        request-anatomy tap marks this at admission and attributes the
        delta at first token as the request's compile stall."""
        return sum(self._compile_s.values())

    def host_gap_fraction(self, kind: str) -> float:
        """Median host-gap share of one dispatch interval for ``kind``
        (gap / (gap + in-flight)), in [0, 1]. The anatomy decomposition
        uses it to carve host_gap out of decode compute. 0.0 before the
        first sample."""
        flight = self._p(self._flight[kind], 0.5)
        if flight is None:
            return 0.0
        gap = self._p(self._gap[kind], 0.5) or 0.0
        return gap / (gap + flight) if (gap + flight) > 0 else 0.0

    # ------------------------------------------------------------- summary
    @staticmethod
    def _p(samples, q) -> float | None:
        v = percentile(list(samples), q)
        return round(v, 6) if v is not None else None

    def summary(self) -> dict:
        """Per-kind stats over the recent reservoir window — the
        ``engine.metrics()["dispatch"]`` mirror. Every kind is always present (count 0, None
        percentiles before its first dispatch) so consumers see a
        stable shape."""
        out = {}
        for k in DISPATCH_KINDS:
            out[k] = {
                "count": self._count[k],
                "host_gap_p50_s": self._p(self._gap[k], 0.5),
                "host_gap_p99_s": self._p(self._gap[k], 0.99),
                "in_flight_p50_s": self._p(self._flight[k], 0.5),
                "in_flight_p99_s": self._p(self._flight[k], 0.99),
                "compile_misses": self._compile_misses[k],
                "compile_total_s": round(self._compile_s[k], 6),
            }
        return out

    def span_attrs(self, kind: str, **extra) -> dict:
        """Attrs for the existing decode/prefill spans (sim/fit.py fits
        per-dispatch service times from these): median in-flight and
        host-gap for the kind, or {} before the first sample."""
        flight = self._p(self._flight[kind], 0.5)
        if flight is None:
            return {}
        gap = self._p(self._gap[kind], 0.5)
        return {
            "dispatch_p50_s": flight,
            "host_gap_p50_s": gap if gap is not None else 0.0,
            **extra,
        }
