"""Engine flight recorder + no-progress watchdog (docs/observability.md
"Engine flight recorder & watchdog").

When the engine loop wedges, spans and counters tell you nothing — the
request that hangs never finishes a stage. The flight recorder is the
black box for that case: a bounded in-memory ring of engine-loop events
(admission, dispatch/consume, stall start/end, preemption, lease
lifecycle, spec accept/rewind, chain breaks) that costs one deque
append per event while everything is healthy, and is dumped to JSONL —
with a scheduler/slot/page snapshot — exactly when something isn't:

- the **watchdog** thread detects no-progress-while-work-is-queued and
  dumps once per stall episode;
- **SIGUSR1** dumps every registered engine's ring on demand
  (``install_sigusr1`` / the ``dynamo_exp_tpu.run`` handler);
- an **engine-loop crash** dumps on the way out.

Event payloads are deterministic given a deterministic engine run (the
chaos suite proves bit-identical event sequences across same-seed
runs); only the per-event wall timestamp ``t`` differs between runs.
``llmctl flight <file>`` renders a dump as a per-slot timeline the way
``llmctl trace`` renders spans.

What the host did *to* the loop is in the ring too, as notes
(:data:`HOST_KINDS`; ``FlightRecorder.note``): a collector pause with
the thread it ran on, and a whole-process stall with the dispatch it
fell in. :class:`HostWitness` writes them (docs/observability.md "Off
the loop thread"). They are no part of the deterministic sequence: a
note takes a place in the ring and no ``seq``.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import tempfile
import threading
import time
from collections import deque

logger = logging.getLogger(__name__)

# Kinds of ring entries that are notes (``FlightRecorder.note``): what
# a witness outside the loop saw. A reader that compares two runs'
# event sequences leaves them out.
HOST_KINDS = ("gc_pause", "host_stall")
# Why the stall witness woke late (``dynamo_host_stall_seconds_total``'s
# ``cause``, the ``dynstall/<cause>`` names of a trace).
STALL_CAUSES = ("busy", "descheduled")
WITNESS_INTERVAL_S = 0.02  # the witness's sleep
STALL_LATE_S = 0.1  # a wake later than this is a stall
# A collection is noted in the ring when it is of the oldest generation
# or took this long: the young ones run many times a second.
GC_NOTE_S = 0.01
GC_PREFIX, STALL_PREFIX = "dyngc/", "dynstall/"
_GC_EVENTS = tuple(f"{GC_PREFIX}gen{g}" for g in range(3))


def default_dump_path() -> str:
    """``DYN_FLIGHT_DUMP`` or a per-process file under the tempdir."""
    return os.environ.get("DYN_FLIGHT_DUMP", "") or os.path.join(
        tempfile.gettempdir(), f"dynamo_flight_{os.getpid()}.jsonl"
    )


class FlightRecorder:
    """Bounded ring of engine-loop events.

    ``record`` is the hot-path call: one lock-guarded list append (the
    ring is a plain list + head index so ``seq`` numbering and eviction
    stay atomic). ``data`` must be JSON-serializable and — for the
    determinism guarantee — free of wall-clock values and run-global
    ids; the recorder adds ``seq`` and ``t`` itself.
    """

    def __init__(self, capacity: int = 2048):
        self.capacity = max(capacity, 16)
        self._lock = threading.Lock()
        self._ring: list[dict] = []
        self._head = 0  # index of the oldest event once the ring wraps
        self.seq = 0  # total events ever recorded (watchdog progress)

    def record(self, kind: str, **data) -> None:
        ev = {"seq": 0, "t": time.time(), "kind": kind, **data}  # dynlint: determinism(recorder-owned wall stamp)
        self._append(ev, numbered=True)

    def note(self, kind: str, **data) -> None:
        """An observation from outside the loop (a kind of
        :data:`HOST_KINDS`), from any thread: it takes the ring's next
        place and no sequence number, so the loop's own events keep the
        numbers a same-seed run gives them. ``after`` is the number of
        the newest loop event before it (-1: none yet)."""
        ev = {"after": 0, "t": time.time(), "kind": kind, **data}  # dynlint: determinism(recorder-owned wall stamp)
        self._append(ev, numbered=False)

    def _append(self, ev: dict, numbered: bool) -> None:
        with self._lock:
            if numbered:
                ev["seq"] = self.seq
                self.seq += 1
            else:
                ev["after"] = self.seq - 1
            if len(self._ring) < self.capacity:
                self._ring.append(ev)
            else:
                self._ring[self._head] = ev
                self._head = (self._head + 1) % self.capacity

    def snapshot(self) -> list[dict]:
        """Events oldest-first (a copy; safe from any thread)."""
        with self._lock:
            return self._ring[self._head :] + self._ring[: self._head]

    def clear(self) -> None:
        """Drop all events and restart ``seq`` at 0 — a warmed-up test
        harness clears the ring so dumps compare across runs whose
        warmup event counts raced differently."""
        with self._lock:
            self._ring = []
            self._head = 0
            self.seq = 0

    # ---------------------------------------------------------------- dump
    def dump(
        self, path: str, reason: str, snapshot: dict | None = None
    ) -> str:
        """Append one dump block (header, events, snapshot) to ``path``.
        Never raises into the caller — a failing dump must not worsen
        whatever triggered it."""
        events = self.snapshot()
        try:
            dirname = os.path.dirname(os.path.abspath(path))
            os.makedirs(dirname, exist_ok=True)
            with open(path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps(
                        {
                            "type": "flight_header",
                            "reason": reason,
                            "t": time.time(),  # dynlint: determinism(recorder-owned wall stamp)
                            "pid": os.getpid(),
                            "events": len(events),
                        }
                    )
                    + "\n"
                )
                for ev in events:
                    f.write(
                        json.dumps({"type": "flight_event", **ev}) + "\n"
                    )
                if snapshot is not None:
                    f.write(
                        json.dumps(
                            {
                                "type": "flight_snapshot",
                                "t": time.time(),  # dynlint: determinism(recorder-owned wall stamp)
                                **snapshot,
                            }
                        )
                        + "\n"
                    )
        except Exception:  # noqa: BLE001 - diagnostics must not cascade
            logger.exception("flight dump to %s failed", path)
        else:
            logger.warning(
                "flight recorder dumped %d events to %s (reason=%s)",
                len(events), path, reason,
            )
        return path


class Watchdog:
    """No-progress detector over an opaque progress counter.

    Fires ``dump_fn(reason)`` once per stall episode when ``has_work()``
    has been true and ``progress()`` unchanged for ``stall_s`` seconds;
    re-arms as soon as progress moves again. Progress is whatever
    monotonically-increasing integer the owner bumps on real forward
    motion (the engine bumps per loop iteration that dispatched,
    consumed, or admitted), so a loop stuck compiling, spinning on a dry
    pool with nothing to preempt, or deadlocked all look the same:
    frozen counter, queued work.
    """

    def __init__(
        self,
        stall_s: float,
        progress,  # () -> int
        has_work,  # () -> bool
        dump_fn,  # (reason: str) -> None
        poll_s: float | None = None,
    ):
        self.stall_s = stall_s
        self._progress = progress
        self._has_work = has_work
        self._dump = dump_fn
        self._poll_s = poll_s if poll_s is not None else max(stall_s / 4, 0.05)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.dumps = 0

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="engine-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def _run(self) -> None:
        last = self._progress()
        since = time.monotonic()
        fired = False
        while not self._stop.wait(self._poll_s):
            try:
                cur = self._progress()
                busy = self._has_work()
            except Exception:  # owner mid-teardown; try again next poll
                continue
            now = time.monotonic()
            if cur != last or not busy:
                last = cur
                since = now
                fired = False
                continue
            if not fired and now - since >= self.stall_s:
                fired = True  # once per episode
                self.dumps += 1
                try:
                    self._dump("watchdog")
                except Exception:  # noqa: BLE001
                    logger.exception("watchdog dump failed")


class HostWitness:
    """What happens to the process while the engine loop waits: the
    cyclic collector's pauses and stalls of the whole process, as
    counters, as notes in every attached flight ring and, while a
    profiler session is on, as ``dyngc/gen<n>`` and ``dynstall/<cause>``
    events (docs/observability.md "Off the loop thread").

    One a process (:func:`host_witness`): ``gc.callbacks`` and the
    process's CPU clock are the process's. It runs while a ring is
    attached: the first ``attach`` installs one ``gc.callbacks`` hook
    and starts one daemon thread, the last ``detach`` takes both away.

    **Collections.** The hook times each collection between its
    ``start`` and ``stop`` calls, on the thread that ran it (collections
    do not nest): ``dynamo_gc_pause_seconds_total{generation}``,
    ``dynamo_gc_collections_total{generation}``,
    ``dynamo_gc_pause_max_seconds``, and a ``gc_pause`` note for one of
    the oldest generation or of :data:`GC_NOTE_S`. A collection starts
    between any two bytecodes of whatever thread is running, inside a
    ``with lock:`` as well, so the hook takes no lock: it leaves what it
    timed in a deque, and the witness's thread books it at its next wake
    (:meth:`publish`).

    **Stalls.** The thread sleeps :data:`WITNESS_INTERVAL_S` and
    measures how late it woke. Later than :data:`STALL_LATE_S`, the
    lateness goes to ``dynamo_host_stall_seconds_total{cause}``:
    ``busy`` where ``time.process_time()`` advanced by at least half of
    it over the gap (the process ran: another thread held the GIL),
    ``descheduled`` where it did not (the process did not run). The
    ``host_stall`` note carries what each ring's ``context()`` says was
    in flight. A trace event cannot be dated back, so ``dynstall/`` is a
    mark at the wake whose ``late_ns`` says how far back it reaches."""

    def __init__(
        self, telemetry=None,
        interval_s: float = WITNESS_INTERVAL_S, late_s: float = STALL_LATE_S,
    ):
        self._tel = telemetry
        self.interval_s, self.late_s = interval_s, late_s
        self._lock = threading.Lock()
        self._sinks: dict[int, tuple] = {}  # handle -> (flight, context)
        self._next = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._gc_t0 = 0.0
        self._gc_ann = None
        self._gc_seen: deque = deque()  # (generation, seconds, thread, collected)
        self._annotation = None  # dispatch.trace_annotation(), at attach
        self.gc_pause_max_s = 0.0
        self._gc_c = self._stall_c = None
        if telemetry is not None:
            # Every child now: a series that is there reads 0, one that
            # is not reads as a program without it.
            self._gc_c = {
                g: (
                    telemetry.gc_pause_seconds.labels(str(g)),
                    telemetry.gc_collections.labels(str(g)),
                )
                for g in range(3)
            }
            self._stall_c = {
                c: (
                    telemetry.host_stall_seconds.labels(c),
                    telemetry.host_stalls.labels(c),
                )
                for c in STALL_CAUSES
            }

    # ---------------------------------------------------------- lifecycle
    def attach(self, flight: FlightRecorder, context=None) -> int:
        """Note into ``flight`` from now on; ``context() -> dict`` is
        what a ``host_stall`` note says was in flight. Returns the
        handle for :meth:`detach`."""
        with self._lock:
            self._next += 1
            self._sinks[self._next] = (flight, context)
            if self._thread is None:
                from .dispatch import trace_annotation

                self._annotation = trace_annotation()
                gc.callbacks.append(self._on_gc)
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="host-witness", daemon=True
                )
                self._thread.start()
            return self._next

    def detach(self, handle: int) -> None:
        with self._lock:
            self._sinks.pop(handle, None)
            if self._sinks or self._thread is None:
                return
            thread, self._thread = self._thread, None
            if self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)
            self._stop.set()
        thread.join(timeout=5)
        self.publish()

    def _note(self, kind: str, with_context: bool = False, **data) -> None:
        for flight, context in list(self._sinks.values()):
            extra = {}
            if with_context and context is not None:
                try:
                    extra = context() or {}
                except Exception:  # noqa: BLE001 - owner mid-teardown
                    extra = {}
            flight.note(kind, **data, **extra)

    # -------------------------------------------------------- collections
    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            a = self._annotation
            if a is not None and a.is_enabled():
                self._gc_ann = a(_GC_EVENTS[info["generation"]])
                self._gc_ann.__enter__()
            self._gc_t0 = time.monotonic()
            return
        seconds = time.monotonic() - self._gc_t0
        if self._gc_ann is not None:
            self._gc_ann.__exit__(None, None, None)
            self._gc_ann = None
        gen = info["generation"]
        noted = gen == 2 or seconds >= GC_NOTE_S
        self._gc_seen.append((
            gen, seconds,
            threading.current_thread().name if noted else None,
            info.get("collected", 0),
        ))

    def publish(self) -> None:
        """What the hook has timed since the last call, to the counters
        and the rings (the witness's thread, each wake; not the hook)."""
        while self._gc_seen:
            gen, seconds, thread, collected = self._gc_seen.popleft()
            if self._gc_c is not None:
                pause_c, count_c = self._gc_c[gen]
                pause_c.inc(seconds)
                count_c.inc()
            if seconds > self.gc_pause_max_s:
                self.gc_pause_max_s = seconds
                if self._tel is not None:
                    self._tel.gc_pause_max.set(seconds)
            if thread is not None:
                self._note(
                    "gc_pause", generation=gen, seconds=round(seconds, 6),
                    thread=thread, collected=collected,
                )

    # ------------------------------------------------------------- stalls
    def _run(self) -> None:
        clock, cpu_clock = time.monotonic, time.process_time
        t0, cpu0 = clock(), cpu_clock()
        while not self._stop.wait(self.interval_s):
            now = clock()
            self.publish()
            late = now - t0 - self.interval_s
            if late > self.late_s:
                cpu = cpu_clock()
                self.stalled(late, cpu - cpu0)
                t0, cpu0 = clock(), cpu
            else:
                t0, cpu0 = now, cpu_clock()

    def stalled(self, late_s: float, cpu_s: float) -> str:
        """A wake ``late_s`` late, over a gap in which the process's CPU
        clock advanced by ``cpu_s``: booked, noted, marked; returns the
        cause. (The thread's own call; a test's, with a clock it made.)"""
        cause = "busy" if cpu_s >= 0.5 * late_s else "descheduled"
        if self._stall_c is not None:
            seconds_c, count_c = self._stall_c[cause]
            seconds_c.inc(late_s)
            count_c.inc()
        # Rare (a wake over 100 ms late) and what an operator reading a
        # slow minute's log wants beside it.
        logger.warning(
            "host stall: the process lost %.3f s (%s; its CPU clock moved "
            "%.3f s)", late_s, cause, cpu_s,
        )
        self._note(
            "host_stall", with_context=True, cause=cause,
            start=time.time() - late_s,
            seconds=round(late_s, 6), process_cpu_s=round(cpu_s, 6),
        )
        a = self._annotation
        if a is not None and a.is_enabled():
            with a(STALL_PREFIX + cause, late_ns=int(late_s * 1e9)):
                pass
        return cause


_WITNESS: HostWitness | None = None
_witness_lock = threading.Lock()


def host_witness() -> HostWitness:
    """The process's :class:`HostWitness`, on the process's hub."""
    global _WITNESS
    with _witness_lock:
        if _WITNESS is None:
            from .spans import get_telemetry

            _WITNESS = HostWitness(get_telemetry())
        return _WITNESS


# ------------------------------------------------------- process registry
# Live engines register their dump callables so SIGUSR1 (and operators
# embedding several engines in one process) can dump every ring at once.
_dumpers: dict[int, object] = {}
_dumpers_lock = threading.Lock()


def register_dumper(dump_fn) -> int:
    """Register a ``(reason) -> None`` dump callable; returns a handle
    for :func:`unregister_dumper`."""
    with _dumpers_lock:
        handle = id(dump_fn)
        _dumpers[handle] = dump_fn
        return handle


def unregister_dumper(handle: int) -> None:
    with _dumpers_lock:
        _dumpers.pop(handle, None)


def dump_all(reason: str) -> int:
    """Dump every registered recorder; returns how many dumped."""
    with _dumpers_lock:
        fns = list(_dumpers.values())
    for fn in fns:
        try:
            fn(reason)
        except Exception:  # noqa: BLE001
            logger.exception("flight dump_all(%s) failed for one engine", reason)
    return len(fns)


def install_sigusr1() -> bool:
    """Chain a SIGUSR1 handler that dumps all registered recorders
    (keeps any existing handler). Main-thread only; returns False where
    signals aren't available."""
    import signal

    try:
        prev = signal.getsignal(signal.SIGUSR1)

        def handler(signum, frame):
            dump_all("sigusr1")
            if callable(prev):
                prev(signum, frame)

        signal.signal(signal.SIGUSR1, handler)
        return True
    except (ValueError, OSError, AttributeError):
        return False


# ------------------------------------------------------------- load/render
def load_dumps(path: str) -> list[dict]:
    """Parse a dump file into blocks:
    ``{"header": ..., "events": [...], "snapshot": ...|None}`` per dump
    (a file accumulates one block per dump). Corrupt lines (torn write
    at crash) are skipped."""
    blocks: list[dict] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                logger.warning("skipping corrupt flight line")
                continue
            t = d.get("type")
            if t == "flight_header":
                blocks.append({"header": d, "events": [], "snapshot": None})
            elif blocks and t == "flight_event":
                blocks[-1]["events"].append(d)
            elif blocks and t == "flight_snapshot":
                blocks[-1]["snapshot"] = d
    return blocks


def _event_label(ev: dict) -> str:
    skip = {"type", "seq", "t", "kind", "slot", "req"}
    details = " ".join(
        f"{k}={ev[k]}" for k in sorted(ev) if k not in skip
    )
    return f"{ev['kind']}({details})" if details else ev["kind"]


def render_flight(block: dict) -> str:
    """Per-slot timeline of one dump block, ``llmctl trace`` style:
    batch-level events (dispatch/consume/chain breaks/leases) on an
    ``engine`` lane, per-request events on the slot they were bound to,
    and the snapshot's slot table — stalled slots flagged — at the
    bottom."""
    header = block.get("header") or {}
    events = block.get("events") or []
    snapshot = block.get("snapshot")
    if not events and snapshot is None:
        return "empty flight dump"
    t0 = min((ev["t"] for ev in events), default=header.get("t", 0.0))
    span = max((ev["t"] for ev in events), default=t0) - t0
    lines = [
        f"flight dump — reason={header.get('reason', '?')}, "
        f"{len(events)} events, {span * 1e3:.1f}ms span"
    ]
    # req -> slot from admit events (finish/preempt events carry slot
    # too; first sighting wins so a reused slot keeps per-request lanes
    # distinct enough to read).
    req_slot: dict[str, object] = {}
    for ev in events:
        if (
            "req" in ev
            and ev.get("slot") is not None
            and ev["req"] not in req_slot
        ):
            req_slot[ev["req"]] = ev["slot"]
    lanes: dict[object, list[dict]] = {}
    for ev in events:
        # An explicit slot=None (e.g. a finish for work never bound to
        # a slot) falls back to the request's admitted lane, not a
        # bogus "slot None" lane.
        slot = ev.get("slot")
        if slot is None:
            slot = req_slot.get(ev.get("req"), "engine")
        lanes.setdefault(slot, []).append(ev)

    def lane_key(k):
        return (1, k) if isinstance(k, int) else (0, str(k))

    for slot in sorted(lanes, key=lane_key):
        evs = lanes[slot]
        name = "engine" if slot == "engine" else f"slot {slot}"
        reqs = sorted({ev["req"] for ev in evs if "req" in ev})
        head = f"{name:<8}" + (f" [{', '.join(reqs)}]" if reqs else "")
        lines.append(head)
        for ev in evs:
            lines.append(
                f"  {ev['t'] - t0:9.3f}s  {_event_label(ev)}"
            )
    if snapshot is not None:
        lines.append("snapshot:")
        for k in sorted(snapshot):
            if k in ("type", "t", "slots"):
                continue
            lines.append(f"  {k}={snapshot[k]}")
        for s in snapshot.get("slots") or []:
            flag = "  STALLED" if s.get("stalled") else ""
            lines.append(
                f"  slot {s.get('slot')}  req={s.get('req')} "
                f"state={s.get('state')} generated={s.get('generated')} "
                f"pages={s.get('pages')}{flag}"
            )
    return "\n".join(lines)
