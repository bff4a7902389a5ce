"""The life of an admission (docs/observability.md): phase ``admit`` by
its seven parts, opened in the scheduler and the page manager through an
opener they are handed, and the three series counted at the same
boundaries (``dynamo_admit_attempts_total``, ``dynamo_admit_seconds``,
``dynamo_kv_pages_taken_total``).
"""

import ast
import asyncio
import contextlib
import inspect
import time

import pytest
from prometheus_client import CollectorRegistry

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine, kv_manager, scheduler
from dynamo_exp_tpu.engine.kv_manager import KvPageManager, no_part
from dynamo_exp_tpu.engine.scheduler import ADMIT_OUTCOMES, Scheduler, Sequence
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import Telemetry, get_telemetry
from dynamo_exp_tpu.telemetry.dispatch import (
    OTHER_PART,
    PHASE_PARTS,
    DispatchProfiler,
)

PS = 4
POOL = 520  # a 400-page prompt fits once: the next one evicts the first's
ADMIT = PHASE_PARTS["admit"]


def _prompt(pages: int, salt: int) -> list[int]:
    """A prompt that ends a token short of ``pages`` pages and shares no
    block with another salt's."""
    return [(salt * 7 + i * i + i // 3) % 250 + 3 for i in range(pages * PS - 1)]


def _value(name: str, **labels) -> float:
    got = get_telemetry().registry.get_sample_value(name, labels)
    return 0.0 if got is None else got


def _attempts() -> dict[str, float]:
    return {o: _value("dynamo_admit_attempts_total", outcome=o) for o in ADMIT_OUTCOMES}


async def _generate(engine, prompt, max_tokens=2):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    return [item async for item in stream]


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(
        model=TINY, max_decode_slots=2, page_size=PS, num_pages=POOL,
        max_model_len=1664, eos_token_ids=[], kv_dtype="float32", decode_window=4,
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    yield engine
    engine.stop()


# ------------------------------------------------------------------ the names
def test_the_parts_of_admit_are_the_seven_and_the_opener_knows_no_other():
    assert ADMIT == ("schedule", "hash", "match", "take", "register", "bind", "record")
    prof = DispatchProfiler(Telemetry(CollectorRegistry()))
    opener = prof.part_opener("admit")
    assert {opener(name)._label for name in ADMIT} == {
        f"dynpart/admit.{name}" for name in ADMIT
    }
    with pytest.raises(KeyError):
        opener("finish")  # another phase's
    with pytest.raises(KeyError):
        opener(OTHER_PART)  # what is left, not a part to open


def test_an_openers_stats_go_to_the_one_event_they_were_given_for():
    """``req`` and ``prompt_tokens`` ride the ``dynpart/admit.hash`` event
    of an attempt as its stats, as ``dynstall/`` carries ``late_ns``; the
    next event under the same name has none."""
    made = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **stats):
            super().__init__()
            made.append((name, stats))

    prof = DispatchProfiler(Telemetry(CollectorRegistry()))
    prof._annotation = Annotation
    opener = prof.part_opener("admit")
    with prof.phase("admit"):
        with opener("hash", req="r-1", prompt_tokens=9000):
            pass
        with opener("hash"):
            pass
        with prof.part("admit", "record"):
            pass
    assert made == [
        ("dyn/admit", {}),
        ("dynpart/admit.hash", {"req": "r-1", "prompt_tokens": 9000}),
        ("dynpart/admit.hash", {}),
        ("dynpart/admit.record", {}),
    ]


@pytest.mark.parametrize("module", [kv_manager, scheduler], ids=["kv_manager", "scheduler"])
def test_the_modules_under_the_loop_import_nothing_of_the_profiler(module):
    """An ``ast`` walk, as dynlint's rules do: the page manager and the
    scheduler are handed an opener and know no ``telemetry.dispatch``."""
    tree = ast.parse(inspect.getsource(module))
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            imported |= {base} | {f"{base}.{a.name}" for a in node.names}
    assert imported and not [name for name in imported if "dispatch" in name], imported
    if module is kv_manager:  # the page manager knows no telemetry at all
        assert not [name for name in imported if "telemetry" in name], imported


# -------------------------------------------------------- the page manager's
class _Recording:
    """An opener that writes down what it was asked to open."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **stats):
        self.opened.append((name, stats))
        return contextlib.nullcontext()


def _allocation_story(opener) -> tuple[list, list, list]:
    """The same askings of two managers must come to the same: a prompt,
    a prompt sharing its first two pages, then a long one that evicts."""
    events = []
    kv = KvPageManager(num_pages=12, page_size=PS, event_cb=events.append)
    kwargs = {} if opener is None else {"part": opener}
    first = kv.allocate_sequence(list(range(10, 26)), 16, "a", **kwargs)
    kv.mark_filled(first.page_ids)
    second = kv.allocate_sequence(
        list(range(10, 18)) + list(range(50, 57)), 16, "b", **kwargs
    )
    kv.mark_filled(second.page_ids)
    kv.release_sequence(first.page_ids)
    kv.release_sequence(second.page_ids)
    third = kv.allocate_sequence(list(range(100, 140)), 16, "c", **kwargs)
    dry = kv.allocate_sequence(list(range(200, 232)), 16, "d", **kwargs)
    out = [first, second, third, dry]
    return out, [(e.kind, list(e.seq_hashes)) for e in events], [
        kv.ledger_check(), dict(kv.pages_taken), dict(kv.prefix_hits),
    ]


def test_allocate_sequence_without_an_opener_is_the_allocation_it_was():
    bare = _allocation_story(None)
    rec = _Recording()
    timed = _allocation_story(rec)
    assert bare == timed
    allocs, events, (ledger, taken, hits) = bare
    assert ledger == [] and allocs[3] is None
    assert allocs[1].cached_len == 2 * PS and allocs[1].page_ids[:2] == allocs[0].page_ids[:2]
    # Twelve pages: 4 + 2 fresh; five of those six park (the second's
    # partial tail goes free), so the third's ten are 7 free and 3 evicted.
    removed = sum(len(h) for kind, h in events if kind == "removed")
    assert taken == {"free": 13, "evicted": 3} and removed == 3
    assert hits["shared"] == 2
    # Every try opens its parts in order; the dry one stops behind match.
    names = [name for name, _ in rec.opened]
    assert names == ["hash", "match", "take", "register"] * 3 + ["hash", "match"]
    assert rec.opened[0] == ("hash", {"req": "a", "prompt_tokens": 16})
    assert all(stats == {} for name, stats in rec.opened if name != "hash")
    assert no_part("hash", req="a") is no_part("take")


# ------------------------------------------------------------ the scheduler's
def _scheduler(num_pages: int, slots: int = 2) -> Scheduler:
    cfg = EngineConfig(
        model=TINY, max_decode_slots=slots, page_size=PS, num_pages=num_pages,
        max_model_len=256, eos_token_ids=[], kv_dtype="float32",
    )
    return Scheduler(cfg, KvPageManager(num_pages, PS))


def _seq(prompt, rid, cancelled: bool = False) -> Sequence:
    return Sequence(
        request_id=rid, prompt=list(prompt), stop=BackendInput(token_ids=list(prompt)),
        emit=lambda *a: None, is_cancelled=lambda: cancelled,
    )


def test_every_turn_of_admit_next_counts_once_by_its_outcome():
    sched = _scheduler(num_pages=8, slots=1)
    rec = _Recording()
    before = _attempts()
    cancelled = _seq(range(5, 9), "gone", cancelled=True)
    too_long = _seq(range(3, 3 + 300), "long")  # past max_model_len
    sched.submit(cancelled)
    sched.submit(too_long)
    sched.submit(_seq(range(10, 30), "fits"))  # five of eight pages
    sched.submit(_seq(range(40, 60), "waits"))
    admitted = sched.admit_next(rec)
    assert admitted.request_id == "fits"
    assert sched.admit_next(rec) is None  # the one slot is taken
    got = {o: v - before[o] for o, v in _attempts().items()}
    assert got == {"admitted": 1, "no_slot": 1, "no_pages": 0, "rejected": 1, "dropped": 1}
    names = [name for name, _ in rec.opened]
    assert names == [
        "schedule", "schedule",  # the cancelled head, the rejection
        "schedule", "hash", "match", "take", "register", "bind",
        "schedule",  # no slot
    ]
    # An empty queue is no attempt: nothing is opened and nothing counted.
    sched.waiting.clear()
    assert sched.admit_next(rec) is None
    assert len(rec.opened) == len(names) and _attempts() == {
        o: before[o] + got[o] for o in ADMIT_OUTCOMES
    }


def test_a_dry_pool_books_hash_and_match_and_admits_nothing():
    """The waste the ratio is about: every try of a prompt the pool cannot
    hold hashes it whole and matches it, counts ``no_pages`` and observes
    nothing in ``dynamo_admit_seconds``."""
    tel = Telemetry(CollectorRegistry())
    prof = DispatchProfiler(tel)
    opener = prof.part_opener("admit")
    sched = _scheduler(num_pages=8)
    holder = _seq(range(10, 30), "holder")
    sched.submit(holder)
    with prof.phase("admit"):
        assert sched.admit_next(opener) is holder  # five pages held
    sched.submit(_seq(range(40, 60), "starved"))  # five more: three are free
    before = _attempts()
    parts0, phase0 = prof.part_seconds(), prof.phase_seconds()["admit"]
    observed = _value("dynamo_admit_seconds_count")
    for _ in range(3):
        with prof.phase("admit"):
            assert sched.admit_next(opener) is None
    got = {o: v - before[o] for o, v in _attempts().items()}
    assert got == {"admitted": 0, "no_slot": 0, "no_pages": 3, "rejected": 0, "dropped": 0}
    assert _value("dynamo_admit_seconds_count") == observed
    parts = {
        p: s - parts0[ph, p] for (ph, p), s in prof.part_seconds().items() if ph == "admit"
    }
    assert parts["hash"] > 0 and parts["match"] > 0 and parts["schedule"] > 0
    assert parts["take"] == parts["register"] == parts["bind"] == parts["record"] == 0.0
    assert sum(parts.values()) == pytest.approx(
        prof.phase_seconds()["admit"] - phase0, rel=1e-6
    )
    assert sched.kv.ledger_check() == []


# ---------------------------------------------------------- through the engine
CASES = [
    # pages, salt, the least of them that come from parked pages
    pytest.param(1, 1, 0, id="1-page"),
    pytest.param(40, 2, 0, id="40-pages"),
    pytest.param(400, 3, 0, id="400-pages"),
    pytest.param(1, 4, 0, id="1-page-again"),
    pytest.param(40, 5, 0, id="40-pages-again"),
    pytest.param(400, 6, 300, id="400-pages-evicting"),
]


@pytest.mark.parametrize("pages, salt, evicting", CASES)
def test_the_parts_of_an_admission_sum_to_the_phase(engine, pages, salt, evicting):
    """Prompts of 1, 40 and 400 pages, the second 400 onto a pool whose
    free pages are the first's, parked: the seven parts and ``other`` sum
    to ``admit``'s seconds, the histogram takes one observation, and the
    evicted pages are exactly the registrations the index lost."""
    prof, kv = engine.profiler, engine.kv
    parts0, phase0 = prof.part_seconds(), prof.phase_seconds()["admit"]
    taken0, registered0 = dict(kv.pages_taken), len(kv._by_hash)
    admitted0 = _value("dynamo_admit_attempts_total", outcome="admitted")
    count0, sum0 = _value("dynamo_admit_seconds_count"), _value("dynamo_admit_seconds_sum")
    assert len(asyncio.run(_generate(engine, _prompt(pages, salt)))) >= 2
    parts = {
        p: s - parts0[ph, p] for (ph, p), s in prof.part_seconds().items() if ph == "admit"
    }
    phase = prof.phase_seconds()["admit"] - phase0
    assert set(parts) == {*ADMIT, OTHER_PART}
    assert all(parts[p] > 0.0 for p in ADMIT), parts
    assert sum(parts.values()) == pytest.approx(phase, rel=0.01)
    assert _value("dynamo_admit_attempts_total", outcome="admitted") == admitted0 + 1
    assert _value("dynamo_admit_seconds_count") == count0 + 1
    # From admit_next's entry to the record's end: inside the phase's seconds.
    assert 0.0 < _value("dynamo_admit_seconds_sum") - sum0 <= phase
    taken = {k: v - taken0[k] for k, v in kv.pages_taken.items()}
    assert taken["free"] + taken["evicted"] == pages + 1  # and the decode's page
    assert taken["evicted"] >= evicting
    # The prompt's full pages joined the index at admission and its last
    # one with the first token; each evicted page left it.
    assert len(kv._by_hash) - registered0 == pages - taken["evicted"]
    assert kv.ledger_check() == []


def test_the_taken_pages_reach_metrics_by_source(engine):
    """Mirrored by delta at the gauge publication, as the prefix hits are:
    after a publication the counter is the page manager's own count."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        got = {s: _value("dynamo_kv_pages_taken_total", source=s) for s in ("free", "evicted")}
        if got["evicted"] >= engine.kv.pages_taken["evicted"] > 0:
            break
        time.sleep(0.05)
    # Process-wide counters: other engines of this process count there too.
    assert got["evicted"] >= engine.kv.pages_taken["evicted"] >= 300
    assert got["free"] >= engine.kv.pages_taken["free"] - 2
