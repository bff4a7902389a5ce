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

**Inside a phase.** Beside its wall time a phase's CPU time
(``time.thread_time()`` at the same two points) goes to
``dynamo_engine_phase_cpu_seconds_total{phase}``: wall less CPU is time
the loop thread did not run (it waited for the GIL, a lock or a system
call, or was descheduled). The four phases a device's idle gaps name
are cut into the named parts of :data:`PHASE_PARTS`
(``dynamo_engine_phase_part_seconds_total{phase,part}``; a phase's
parts and ``other`` sum to its wall seconds). A part that is one
stretch is bracketed by ``part(phase, name)``, which also opens a
``dynpart/<phase>.<name>`` annotation: not ``dyn/``, which a trace
reader takes for a phase, and phases do not nest. Parts that alternate
row by row are summed by the caller from its own clock reads and handed
over once, by ``add_parts``. Either way the seconds wait in the profiler
until the phase's exit books them. The parts of ``admit`` lie in the
scheduler and the page manager, which take ``part_opener("admit")`` as
an argument and import nothing from here (docs/observability.md "The
life of an admission").

**Off the loop thread** (docs/observability.md "Off the loop thread").
What the device waits behind while the loop sits in ``sync`` is not the
loop's: :class:`HttpChunkClock` reads a streamed chunk's way through the
HTTP thread in the parts of :data:`HTTP_PARTS` (``dynhttp/<part>`` in a
trace), and ``telemetry/flight.py``'s ``HostWitness`` the collector's
pauses and the process's stalls (``dyngc/``, ``dynstall/``). Every event
of every family is made by the one class :func:`trace_annotation` hands
out, so all of them lie on the one clock of the host plane.
"""

from __future__ import annotations

import sys
import time
from collections import deque

from .slo import percentile

_ANNOTATION = None


def trace_annotation(load: bool = False):
    """``jax.profiler.TraceAnnotation``: the one class every ``dyn/``,
    ``dynpart/``, ``dynhttp/``, ``dyngc/`` and ``dynstall/`` event of a
    profiler trace is made by (its ``is_enabled()`` says whether a
    session is on). None in a process that has not loaded jax, unless
    ``load``: the telemetry package is also imported by processes that
    never do (frontends, the sim), and none of them can be traced."""
    global _ANNOTATION
    if _ANNOTATION is None and (load or "jax" in sys.modules):
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


# The engine's three device-dispatch kinds. Stable, closed set: the
# prometheus label space and the metrics() mirror key on these names. ``ragged`` covers every compute
# dispatch (the pre-ragged engine split it into prefill / decode /
# spec_verify; sim/fit.py still reads those names from old span and
# bench files for back-compat).
DISPATCH_KINDS = ("ragged", "kv_move", "offload")

# Which compiled program a ragged dispatch ran: a pure-decode ``window``
# (``decode_window`` steps on the device), a ``mixed`` batch (prefill
# chunks, single decode steps, spec spans) or, for a model that generates
# by diffusion over blocks, a ``block`` (every row's block through its
# denoising passes and the pass that writes it: ``engine/block.py``).
# The ``program`` label of the dispatch histograms; the other kinds
# carry ``-``.
PROGRAMS = ("window", "mixed", "block")
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

# Named parts of the phases that a device's idle gaps name. Closed set:
# the ``part`` label space under each ``phase`` (beside ``other``, what
# the named parts leave of the phase) and the ``dynpart/<phase>.<part>``
# names of a trace.
#   consume   tokens       the appends and check_stop
#             pages        register_full_pages
#             emit         _lp_pack, the time-between-tokens stamp, seq.emit
#             first_token  _finish_first_token, whole
#             finish       sched.finish with everything it calls
#   launch    h2d          the jnp.asarray transfers of a dispatch's arguments
#             call         the jitted call until it returns
#   admit     schedule     admit_next outside the allocation: dead heads,
#                          free_slot, _pick_admission, the rejections
#             hash         the prompt's block hashes
#             match        the prefix match down the tiers, the partial
#                          tail, the availability check
#             take         the tiers' fetches, _ref_page, _take_free with
#                          the evictions under it
#             register     the prompt's own pages into the index, and
#                          _register_uploads
#             bind         the sequence's fields, its slot, _on_admitted
#             record       the flight recorder's admission record
#   assemble  plan         _plan_ragged, _plan_chunks, _can_chain
#             build        the host arrays of one dispatch
#             lookup       the compiled-variant lookup
PHASE_PARTS = {
    "consume": ("tokens", "pages", "emit", "first_token", "finish"),
    "launch": ("h2d", "call"),
    "admit": ("schedule", "hash", "match", "take", "register", "bind", "record"),
    "assemble": ("plan", "build", "lookup"),
}
OTHER_PART = "other"

# The thread's CPU clock is a system call (5 µs under a sandboxed kernel,
# where the wall clock is 0.1 µs): a phase that opens within this long of
# the last one's exit starts from the reading taken there. Phases follow
# one another within microseconds, so a run of them costs one reading
# each and not two; what the thread computed in between goes to the
# phase that follows.
CPU_REUSE_S = 50e-6

# The parts of a streamed chunk's way through the HTTP thread. Closed
# set: the ``part`` label space of ``dynamo_http_chunk_seconds_total``
# and the ``dynhttp/<part>`` names of a trace (not ``dyn/``: a trace
# reader takes that for a phase of the loop).
#   detokenize  from the engine's tuple leaving the request's queue to
#               the OpenAI chunk: the engine's and the backend's
#               generators (incremental detokenisation, the stop jail)
#               and the preprocessor's delta
#   frame       validation, the watermark, model_dump, the SSE frame
#   write       the response's write
HTTP_PARTS = ("detokenize", "frame", "write")
HTTP_PREFIX = "dynhttp/"
# A clock that nothing flushes (a frontend whose engine is remote has no
# posting to end on) books itself after this many chunks.
HTTP_FLUSH_CHUNKS = 256

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

    __slots__ = ("_prof", "_name", "_label", "_ann", "_t0", "_cpu0")

    def __init__(self, prof: "DispatchProfiler", name: str):
        self._prof, self._name, self._label = prof, name, f"dyn/{name}"
        self._ann = None
        self._t0 = self._cpu0 = 0.0

    def __enter__(self):
        self._ann = self._prof._annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        self._cpu0 = self._prof._cpu_at(self._t0)
        return self

    def __exit__(self, *exc):
        cpu = self._prof._cpu_now() - self._cpu0
        dt = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self._prof._add_phase(self._name, dt, cpu)
        return False


class _Part:
    """One contiguous stretch of a phase under a part's name (see
    :meth:`DispatchProfiler.part`). One object a part, reused, like
    :class:`_Phase`; parts do not nest either."""

    __slots__ = ("_prof", "_open", "_name", "_label", "_ann", "_t0", "stats")

    def __init__(self, prof: "DispatchProfiler", phase: str, name: str):
        self._prof, self._name = prof, name
        self._open = prof._open_parts[phase]
        self._label = f"dynpart/{phase}.{name}"
        self._ann = None
        self._t0 = 0.0
        # Stats of the next event alone (``part_opener``): the
        # annotation drops them unread while no session is on.
        self.stats: dict | None = None

    def __enter__(self):
        stats = self.stats
        if stats:
            self.stats = None
            self._ann = self._prof._annotation(self._label, **stats)
        else:
            self._ann = self._prof._annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self._open[self._name] += time.monotonic() - self._t0
        self._ann.__exit__(*exc)
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
        self._annotation = trace_annotation(load=True)
        self._phases = {name: _Phase(self, name) for name in PHASES}
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        self._phase_cpu_s = dict.fromkeys(PHASES, 0.0)
        # phase -> {part: seconds of the open (or next) phase, not yet
        # booked}; _add_phase books and zeroes them.
        self._open_parts = {
            phase: dict.fromkeys(parts, 0.0)
            for phase, parts in PHASE_PARTS.items()
        }
        self._parts = {
            (phase, part): _Part(self, phase, part)
            for phase, parts in PHASE_PARTS.items()
            for part in parts
        }
        self._part_s = {
            (phase, part): 0.0
            for phase, parts in PHASE_PARTS.items()
            for part in (*parts, OTHER_PART)
        }
        # The counters' labelled children, looked up once: a phase's
        # exit books up to eight values, a few dozen an iteration.
        self._wall_c = self._cpu_c = self._part_c = None
        if telemetry is not None:
            self._wall_c = {
                p: telemetry.engine_phase_seconds.labels(p) for p in PHASES
            }
            self._cpu_c = {
                p: telemetry.engine_phase_cpu_seconds.labels(p) for p in PHASES
            }
            self._part_c = {
                key: telemetry.engine_phase_part_seconds.labels(*key)
                for key in self._part_s
            }
        self._launch_ann = None  # open between begin() and end() of a launch
        self._launch_cpu0 = 0.0
        # The CPU clock's last reading and the wall clock beside it.
        self._cpu_last = 0.0
        self._cpu_read_at = float("-inf")

    # -------------------------------------------------------------- phases
    def phase(self, name: str) -> _Phase:
        """Context manager round one stretch of the engine loop (a name
        from :data:`PHASES`): its host time goes to
        ``dynamo_engine_phase_seconds_total{phase}`` and, while a
        profiler session is on, a ``dyn/<name>`` event goes to the
        trace. Phases do not nest; ``launch`` is not opened here but by
        ``begin``/``end`` at a ragged dispatch."""
        return self._phases[name]

    def part(self, phase: str, name: str) -> _Part:
        """Context manager round one contiguous stretch of an open
        ``phase`` (names from :data:`PHASE_PARTS`; anything else raises
        ``KeyError``): its seconds go to
        ``dynamo_engine_phase_part_seconds_total{phase,part}`` at the
        phase's exit and, while a profiler session is on, a
        ``dynpart/<phase>.<name>`` event goes to the trace."""
        return self._parts[phase, name]

    def part_opener(self, phase: str):
        """``part`` of one ``phase`` as a callable ``name -> context
        manager``, for code under the loop that is to know nothing of
        this module (the scheduler, the page manager). Keywords become
        the stats of that one ``dynpart/`` event, as ``dynstall/``
        carries ``late_ns``."""
        parts = {name: self._parts[phase, name] for name in PHASE_PARTS[phase]}

        def open_part(name: str, **stats):
            part = parts[name]
            part.stats = stats
            return part

        return open_part

    def add_parts(self, phase: str, **seconds: float) -> None:
        """Seconds of parts that alternate row by row inside an open
        ``phase``, summed by the caller from its own clock reads: one
        call a phase, no annotation."""
        open_ = self._open_parts[phase]
        for name, s in seconds.items():
            if name not in open_:
                raise KeyError((phase, name))
            open_[name] += s

    def _cpu_now(self) -> float:
        """The loop thread's CPU clock, at a phase's exit: read inside
        the wall clock's interval, so that wall less CPU is not negative
        by the reads' own cost."""
        self._cpu_last = cpu = time.thread_time()
        self._cpu_read_at = time.monotonic()
        return cpu

    def _cpu_at(self, now: float) -> float:
        """The same at a phase's entry, ``now`` on the wall clock: the
        last exit's reading when that was under ``CPU_REUSE_S`` ago."""
        if now - self._cpu_read_at < CPU_REUSE_S:
            return self._cpu_last
        return time.thread_time()

    def _add_phase(self, name: str, seconds: float, cpu: float) -> None:
        self._phase_s[name] += seconds
        self._phase_cpu_s[name] += cpu
        if self._wall_c is not None:
            self._wall_c[name].inc(seconds)
            self._cpu_c[name].inc(cpu)
        open_ = self._open_parts.get(name)
        if open_ is None:
            return
        # ``other`` is what the named parts leave, so that the parts of
        # a phase sum to it. Each part was timed inside the phase on the
        # same clock: the rest is negative only by rounding.
        rest = seconds
        for part, s in open_.items():
            if s:
                open_[part] = 0.0
                rest -= s
                self._book_part(name, part, s)
        self._book_part(name, OTHER_PART, max(rest, 0.0))

    def _book_part(self, phase: str, part: str, seconds: float) -> None:
        self._part_s[phase, part] += seconds
        if self._part_c is not None:
            self._part_c[phase, part].inc(seconds)

    def phase_seconds(self) -> dict[str, float]:
        """Host seconds so far by phase (every phase always present)."""
        return dict(self._phase_s)

    def phase_cpu_seconds(self) -> dict[str, float]:
        """The loop thread's CPU seconds so far by phase."""
        return dict(self._phase_cpu_s)

    def part_seconds(self) -> dict[tuple[str, str], float]:
        """Host seconds so far by (phase, part), ``other`` included."""
        return dict(self._part_s)

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
        if program != NO_PROGRAM:
            self._launch_cpu0 = self._cpu_at(now)
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
        launching = self._launch_ann is not None
        cpu = self._cpu_now() - self._launch_cpu0 if launching else 0.0
        now = time.monotonic()
        if launching:
            self._launch_ann.__exit__(None, None, None)
            self._launch_ann = None
            self._add_phase("launch", max(now - t0, 0.0), cpu)
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


class HttpChunkClock:
    """The HTTP thread's handling of streamed chunks, cut into
    :data:`HTTP_PARTS`. One stretch is open from ``taken`` (a tuple left
    its request's queue) to ``idle`` (the generators are back at the
    queue); ``lap`` ends the part it is in and goes on in the next. Wall
    time by ``time.monotonic()``, CPU by ``time.thread_time()`` with the
    chained reads of :data:`CPU_REUSE_S`, summed here and added to the
    counters once a posting by ``flush`` (the engine schedules it behind
    the tasks a posting wakes), so that the parts booked sum to the wall
    time the thread spent with a chunk in hand. While a profiler session
    is on, and only then, each part of each chunk is a
    ``dynhttp/<part>`` event on this thread.

    One asyncio thread calls it. A write that suspends leaves its stretch
    open, and whatever runs meanwhile is booked to it: the sum stays
    right, the parts blur."""

    def __init__(self, telemetry=None):
        self._wall = dict.fromkeys(HTTP_PARTS, 0.0)
        self._cpu = dict.fromkeys(HTTP_PARTS, 0.0)
        self._chunks = 0
        self._part: str | None = None  # of the open stretch; None = idle
        self._t0 = self._cpu0 = 0.0
        self._cpu_last = 0.0
        self._cpu_read_at = float("-inf")
        self._labels = {p: HTTP_PREFIX + p for p in HTTP_PARTS}
        self._ann = None
        self._annotation = trace_annotation()
        self._wall_c = self._cpu_c = self._chunks_c = None
        if telemetry is not None:
            self._wall_c = {
                p: telemetry.http_chunk_seconds.labels(p) for p in HTTP_PARTS
            }
            self._cpu_c = {
                p: telemetry.http_chunk_cpu_seconds.labels(p) for p in HTTP_PARTS
            }
            self._chunks_c = telemetry.http_chunks

    def _open(self, part: str) -> None:
        self._chunks += 1
        self._part = part
        self._t0 = now = time.monotonic()
        if now - self._cpu_read_at < CPU_REUSE_S:
            self._cpu0 = self._cpu_last
        else:
            self._cpu0 = time.thread_time()
        self._annotate(part)

    def _annotate(self, part: str | None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        a = self._annotation
        if part is not None and a is not None and a.is_enabled():
            self._ann = a(self._labels[part])
            self._ann.__enter__()

    def _book(self) -> None:
        """The open stretch so far, to its part."""
        self._cpu_last = cpu = time.thread_time()
        self._cpu_read_at = now = time.monotonic()
        self._wall[self._part] += now - self._t0
        self._cpu[self._part] += cpu - self._cpu0
        self._t0, self._cpu0 = now, cpu

    def taken(self) -> None:
        """A tuple left its request's queue (the engine's generator, and
        again the backend's above it: the same chunk on its way up)."""
        if self._part is None:
            self._open("detokenize")

    def lap(self, part: str) -> None:
        """The chunk goes on in ``part``. With no stretch open (an
        engine without the generators that open one) it opens here."""
        if self._part is None:
            self._open(part)
        elif part != self._part:
            self._book()
            self._part = part
            self._annotate(part)

    def idle(self) -> None:
        """The chunk is handled: the thread goes back to a queue."""
        if self._part is None:
            return
        self._book()
        self._part = None
        self._annotate(None)
        if self._chunks >= HTTP_FLUSH_CHUNKS:
            self.flush()

    def flush(self) -> None:
        """What was summed since the last call, to the counters."""
        n, self._chunks = self._chunks, 0
        if self._chunks_c is None:
            return
        if n:
            self._chunks_c.inc(n)
        for part in HTTP_PARTS:
            wall, cpu = self._wall[part], self._cpu[part]
            if wall:
                self._wall[part] = 0.0
                self._wall_c[part].inc(wall)
            if cpu > 0:
                self._cpu[part] = 0.0
                self._cpu_c[part].inc(cpu)


_HTTP_CLOCK: HttpChunkClock | None = None


def http_chunk_clock() -> HttpChunkClock:
    """The process's :class:`HttpChunkClock`, on the process's hub."""
    global _HTTP_CLOCK
    if _HTTP_CLOCK is None:
        from .spans import get_telemetry

        _HTTP_CLOCK = HttpChunkClock(get_telemetry())
    return _HTTP_CLOCK
