"""Spot-reclamation survival: deadline-bounded live KV migration with
topology-nearest failover and token-identical resume
(docs/fault_tolerance.md "Spot reclamation & live migration").

Layers under test:

- **planner** (pure): ``plan_triage`` orders in-flight sequences
  most-valuable-first, assigns the topology-nearest survivor from
  TransferLedger cost predictions, and only migrates what fits the
  ``grace - margin`` budget under a sequential-transfer model — same
  inputs, same plan, every time (the simulator runs this exact code).
- **lease clamp**: ``migration_lease_ttl_s = max(ttl, grace + margin)``
  keeps the engine-loop reaper from freeing pinned pages mid-transfer
  while the grace clock is still running (reap-race regression on an
  injected clock).
- **routing**: a ``reclaiming`` instance stops receiving new work
  within one watch event, and the KV-aware continuation reselector
  excludes it; a mid-stream chaos reclaim resumes on a survivor with a
  token stream identical to an uninterrupted run.
- **live migration** (real TPUEngine on the CPU mesh, real TCP): the
  dying engine extracts complete KV pages under a clamped lease, ships
  them with their chained block hashes, the survivor's MigrationSink
  parks them as matchable prefix blocks, and the journal continuation
  attaches the transplanted prefix content-addressed — streams stay
  token-identical whether the migration lands (greedy, seeded,
  penalized; spec-on via the ``DYN_SPEC=ngram`` chaos lane) and a
  too-short grace degrades to journal failover with zero lost or
  duplicated tokens.
- **simulator**: a ≥30%-spot fleet under seconds-scale grace holds
  goodput near the all-on-demand control at materially fewer *billed*
  chip-seconds, with a bit-identical event log per seed.

Run with ``make chaos`` (RECLAIM_SEED_SETS) or plain pytest.
"""

import asyncio
import contextlib
import json
import os
import random
import time

import pytest

from dynamo_exp_tpu.parallel.multihost import TOPOLOGY_KEY, TopologyCoordinate
from dynamo_exp_tpu.runtime import (
    Annotated,
    DistributedRuntime,
    PushRouter,
    RouterMode,
)
from dynamo_exp_tpu.runtime.component import RECLAIM_PREFIX
from dynamo_exp_tpu.runtime.reclaim import (
    FAILOVER,
    MIGRATE,
    MigrationSink,
    ReclaimController,
    SequenceSnapshot,
    SurvivorInfo,
    migration_lease_ttl_s,
    nearest_survivor,
    plan_triage,
    survivors_from_instances,
)
from dynamo_exp_tpu.runtime.transports.chaos import (
    ChaosDiscovery,
    ChaosRequestPlane,
    ChaosSchedule,
)
from dynamo_exp_tpu.runtime.transports.inproc import (
    InProcDiscovery,
    InProcRequestPlane,
)
from dynamo_exp_tpu.telemetry import get_telemetry
from dynamo_exp_tpu.telemetry.fleet import TransferLedger

pytestmark = pytest.mark.chaos

SEEDS = tuple(
    int(s) for s in os.environ.get("CHAOS_SEEDS", "7,21,1337").split(",")
)

PROMPT = [11, 12, 13]
MAX_TOKENS = 10


# ------------------------------------------------------------------ helpers
def next_token(context_tokens, seed: int = 0) -> int:
    return (sum(context_tokens) * 31 + len(context_tokens) + seed) % 97 + 3


def make_engine_worker(wid: str, calls: list, step_delay_s: float = 0.0):
    async def handler(request, context=None):
        calls.append(wid)
        toks = list(request["token_ids"])
        sc = request.get("stop_conditions") or {}
        n = sc.get("max_tokens", MAX_TOKENS)
        for _ in range(n):
            if step_delay_s:
                await asyncio.sleep(step_delay_s)
            t = next_token(toks)
            toks.append(t)
            yield Annotated.from_data({"token_ids": [t]}).to_dict()
        yield Annotated.from_data(
            {
                "finish_reason": "length",
                "prompt_tokens": len(request["token_ids"]),
                "completion_tokens": n,
            }
        ).to_dict()

    return handler


def chaos_runtime(schedule: ChaosSchedule) -> DistributedRuntime:
    return DistributedRuntime(
        discovery=ChaosDiscovery(InProcDiscovery(), schedule),
        request_plane=ChaosRequestPlane(InProcRequestPlane(), schedule),
    )


async def serve_two(drt, calls, **worker_kw):
    ep = drt.namespace("reclaim").component("worker").endpoint("generate")
    a = await ep.serve_endpoint(make_engine_worker("a", calls, **worker_kw))
    b = await ep.serve_endpoint(make_engine_worker("b", calls, **worker_kw))
    client = await ep.client()
    await client.wait_for_instances(2, timeout=2)
    return a, b, client


def make_router(client, seed=0, **kw):
    kw.setdefault("mode", RouterMode.ROUND_ROBIN)
    kw.setdefault("backoff_base_s", 0.001)
    return PushRouter(client, rng=random.Random(seed), **kw)


def request_body() -> dict:
    return {
        "token_ids": list(PROMPT),
        "stop_conditions": {"max_tokens": MAX_TOKENS},
    }


async def collect_tokens(stream):
    tokens, final = [], None
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
        if item.get("finish_reason"):
            final = item
    return tokens, final


def expected_greedy() -> list[int]:
    toks = list(PROMPT)
    out = []
    for _ in range(MAX_TOKENS):
        t = next_token(toks)
        toks.append(t)
        out.append(t)
    return out


def _flat_bw_est(bw_bps: float):
    return lambda src, dst, n_bytes: n_bytes / bw_bps


# ----------------------------------------------------- triage planner units
def test_plan_triage_orders_by_value_and_respects_budget():
    """Highest-priority / most-KV-invested first, sequential-transfer
    budget: the plan migrates in value order until the cumulative
    predicted finish would cross ``grace - margin``, then fails over."""
    mb = 1_000_000
    seqs = [
        SequenceSnapshot("r-low", priority=0, full_pages=8, kv_bytes=8 * mb),
        SequenceSnapshot("r-big", priority=1, full_pages=9, kv_bytes=9 * mb),
        SequenceSnapshot("r-sml", priority=1, full_pages=2, kv_bytes=2 * mb),
        SequenceSnapshot("r-hi", priority=2, full_pages=4, kv_bytes=4 * mb),
    ]
    survivors = [SurvivorInfo("s1", 1, migrate_addr="h:1")]
    # 10 MB/s flat: r-hi 0.4s, r-big 0.9s, r-sml 0.2s, r-low 0.8s.
    plan = plan_triage(
        seqs,
        survivors,
        grace_s=2.0,
        origin="dying",
        est_fn=_flat_bw_est(10 * mb),
        margin_s=0.5,  # budget 1.5s
    )
    by_id = {d.seq.request_id: d for d in plan}
    # Value order: hi (2), big (1, more KV), sml (1), low (0).
    assert [d.seq.request_id for d in plan] == [
        "r-hi", "r-big", "r-sml", "r-low"
    ]
    # 0.4 + 0.9 = 1.3 fits; + 0.2 = 1.5 fits exactly; + 0.8 does not.
    assert by_id["r-hi"].action == MIGRATE
    assert by_id["r-big"].action == MIGRATE
    assert by_id["r-sml"].action == MIGRATE
    assert by_id["r-low"].action == FAILOVER
    assert by_id["r-sml"].eta_s == pytest.approx(1.5)
    # Pure + deterministic: same inputs, same plan.
    again = plan_triage(
        seqs, survivors, grace_s=2.0, origin="dying",
        est_fn=_flat_bw_est(10 * mb), margin_s=0.5,
    )
    assert [(d.seq.request_id, d.action, d.eta_s) for d in plan] == [
        (d.seq.request_id, d.action, d.eta_s) for d in again
    ]


def test_nearest_survivor_prefers_topology_then_cost_then_name():
    origin = TopologyCoordinate(slice_id=0, host=0, chip=0)
    near = SurvivorInfo(
        "z-near", 1, topology=TopologyCoordinate(0, 0, 1), migrate_addr="h:1"
    )
    far = SurvivorInfo(
        "a-far", 2, topology=TopologyCoordinate(1, 0, 0), migrate_addr="h:2"
    )
    dest, est = nearest_survivor(
        "dying", origin, [far, near], 1000, _flat_bw_est(1000.0)
    )
    # Cross-chip (distance 1) beats cross-slice (distance 3) even though
    # "a-far" sorts first by name.
    assert dest is near and est == pytest.approx(1.0)
    # Equal distance: the name breaks the tie deterministically.
    twin = SurvivorInfo(
        "a-twin", 3, topology=TopologyCoordinate(0, 0, 2), migrate_addr="h:3"
    )
    dest, _ = nearest_survivor(
        "dying", origin, [near, twin], 1000, _flat_bw_est(1000.0)
    )
    assert dest is twin  # "a-twin" < "z-near"


def test_plan_triage_too_short_grace_degrades_to_journal():
    """Grace at or under the safety margin leaves no transfer budget:
    everything rides the journal — never a hang, never a migration that
    would blow the SIGKILL deadline."""
    seqs = [SequenceSnapshot("r1", kv_bytes=100), SequenceSnapshot("r2")]
    survivors = [SurvivorInfo("s1", 1, migrate_addr="h:1")]
    plan = plan_triage(
        seqs, survivors, grace_s=0.2, origin="o",
        est_fn=_flat_bw_est(1e6), margin_s=1.0,
    )
    assert [d.action for d in plan] == [FAILOVER, FAILOVER]
    # No survivors at all: same degradation.
    plan = plan_triage(
        seqs, [], grace_s=30.0, origin="o", est_fn=_flat_bw_est(1e6)
    )
    assert [d.action for d in plan] == [FAILOVER, FAILOVER]


def test_survivors_from_instances_excludes_self_draining_reclaiming():
    from dynamo_exp_tpu.runtime.transports.base import (
        EndpointAddress,
        InstanceInfo,
    )

    addr = EndpointAddress("n", "c", "e")

    def info(iid, **md):
        return InstanceInfo(address=addr, instance_id=iid, metadata=md)

    infos = [
        info(1, instance="self"),
        info(2, instance="ok", migrate_addr="h:2", **{TOPOLOGY_KEY: "0/1/0"}),
        info(3, instance="draining", draining=True),
        info(4, instance="reclaiming", reclaiming=True),
        info(5),  # no metadata: still a journal-failover target
    ]
    out = survivors_from_instances(infos, self_id=1)
    assert [s.instance_id for s in out] == [2, 5]
    assert out[0].migrate_addr == "h:2"
    assert out[0].topology == TopologyCoordinate(slice_id=0, host=1, chip=0)
    assert out[1].instance == "5" and out[1].migrate_addr == ""


# ------------------------------------------------- lease clamp + reap race
def test_migration_lease_ttl_clamps_past_grace():
    # Disagg handoff TTLs are tuned well under a reclaim grace window.
    assert migration_lease_ttl_s(0.25, 5.0, margin_s=1.0) == 6.0
    # An already-long TTL is left alone.
    assert migration_lease_ttl_s(30.0, 5.0, margin_s=1.0) == 30.0


def test_lease_clamp_prevents_midtransfer_reap_race():
    """Regression: with the raw config TTL the reaper frees the pinned
    pages while the grace clock is still running (pages a dispatched
    gather may still read); the clamped TTL keeps them pinned strictly
    past any send the deadline permits."""
    from dynamo_exp_tpu.engine.kv_manager import KvPageManager

    now = [0.0]
    mgr = KvPageManager(num_pages=8, page_size=8, clock=lambda: now[0])
    pids = [mgr.allocate_page() for _ in range(3)]
    cfg_ttl, grace, margin = 0.25, 2.0, 0.5

    # Control: the unclamped TTL reaps mid-grace — the race.
    raced = mgr.grant_lease(pids, cfg_ttl)
    now[0] = 0.3  # past cfg ttl, well inside the grace window
    assert mgr.reap_expired() == 3
    assert not mgr.confirm_lease(raced)  # already gone

    # Clamped: pinned through the whole window (+margin), reaped after.
    pids = [mgr.allocate_page() for _ in range(3)]
    mgr.grant_lease(pids, migration_lease_ttl_s(cfg_ttl, grace, margin))
    now[0] = 0.3 + cfg_ttl  # the raced instant, relative to grant
    assert mgr.reap_expired() == 0
    now[0] = 0.3 + grace + margin - 1e-6  # last pre-deadline instant
    assert mgr.reap_expired() == 0
    now[0] = 0.3 + grace + margin + 0.01  # SIGKILL has landed; reap away
    assert mgr.reap_expired() == 3


def test_transfer_ledger_cold_start_default_bandwidth():
    """A never-observed link answers at the cold-start prior instead of
    None — a fresh fleet's first triage must be able to price transfers
    before the first real sample lands on the ledger."""
    led = TransferLedger(default_bandwidth_bps=100e6)
    assert led.estimate_transfer_s("a", "b", 50_000_000) == pytest.approx(0.5)
    # A real observation overrides the prior for that link only.
    led.record("a", "b", n_bytes=10_000_000, duration_s=1.0)
    assert led.estimate_transfer_s("a", "b", 10_000_000) == pytest.approx(
        1.0, rel=0.2
    )
    assert led.estimate_transfer_s("a", "c", 50_000_000) == pytest.approx(0.5)
    # Prior disabled: unknown links genuinely unpriceable.
    assert TransferLedger(default_bandwidth_bps=0).estimate_transfer_s(
        "x", "y", 1
    ) is None


# ------------------------------------------------------- routing exclusion
async def test_no_new_request_lands_on_reclaiming_instance():
    """The ``llmctl reclaim`` KV write flips the instance to
    ``reclaiming`` within one watch event; routers stop sending new
    work while the in-flight stream finishes untouched."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls, step_delay_s=0.02)
    router = make_router(client)

    inflight = asyncio.ensure_future(
        collect_tokens(await router.generate(request_body()))
    )
    await asyncio.sleep(0.01)
    assert calls == ["a"]

    before = get_telemetry().reclaim_events.labels("notice")._value.get()
    await drt.discovery.kv_put(
        f"{RECLAIM_PREFIX}{a.instance_id}",
        json.dumps({"grace_s": 3.5}).encode(),
    )
    for _ in range(200):
        live = {i.instance_id: i for i in client.instances}
        got = live.get(a.instance_id)
        if got is not None and got.metadata.get("reclaiming"):
            break
        await asyncio.sleep(0.005)
    else:
        pytest.fail("reclaim metadata never reached the client")
    assert a.is_reclaiming
    assert a.info.metadata.get("reclaim_grace_s") == 3.5
    assert get_telemetry().reclaim_events.labels(
        "notice"
    )._value.get() == before + 1

    # New work only reaches the survivor.
    for _ in range(4):
        tokens, final = await collect_tokens(await router.generate(request_body()))
        assert tokens == expected_greedy()
    assert set(calls[1:]) == {"b"}

    # The in-flight stream on the reclaiming instance finished clean.
    tokens, final = await asyncio.wait_for(inflight, 5)
    assert tokens == expected_greedy()
    assert final["finish_reason"] == "length"
    await drt.close()


async def test_llmctl_reclaim_command_drives_worker_reclaim():
    """The subcommand validates liveness, writes the grace-tagged
    notice, and the worker's watch consumes it."""
    import argparse

    from dynamo_exp_tpu.llmctl import reclaim_instance

    drt = DistributedRuntime.detached()
    ep = drt.namespace("reclaim").component("worker").endpoint("generate")
    a = await ep.serve_endpoint(make_engine_worker("a", []))

    ns = argparse.Namespace(instance_id=999999, grace_s=2.0)
    assert await reclaim_instance(drt, ns) == 1
    assert await drt.discovery.kv_get(f"{RECLAIM_PREFIX}999999") is None

    ns = argparse.Namespace(instance_id=a.instance_id, grace_s=2.0)
    assert await reclaim_instance(drt, ns) == 0
    for _ in range(200):
        if a.is_reclaiming:
            break
        await asyncio.sleep(0.005)
    assert a.is_reclaiming and a.is_draining  # legacy drain gates hold
    # The notice is consumed — intents must not pile up.
    for _ in range(200):
        if await drt.discovery.kv_get(
            f"{RECLAIM_PREFIX}{a.instance_id}"
        ) is None:
            break
        await asyncio.sleep(0.005)
    assert await drt.discovery.kv_get(
        f"{RECLAIM_PREFIX}{a.instance_id}"
    ) is None
    await drt.close()


async def test_continuation_reselector_excludes_reclaiming_instance():
    """The KV-aware reselector (KvPushRouter._reselect) folds
    ``unavailable_ids`` — which treats ``reclaiming`` like draining —
    into the exclusion set, so a continuation can never land back on
    the dying instance."""
    from dynamo_exp_tpu.kv_router.router import KvPushRouter

    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client)
    await a.reclaim(grace_s=1.0)
    for _ in range(200):
        if a.instance_id in router.unavailable_ids():
            break
        await asyncio.sleep(0.005)
    assert a.instance_id in router.unavailable_ids()

    seen: dict = {}

    class FakeKvRouter:
        async def schedule(self, token_ids, exclude=frozenset()):
            seen["exclude"] = set(exclude)

            class R:
                worker_id = b.instance_id
                overlap_blocks = 0

            return R()

    kvp = KvPushRouter(router, FakeKvRouter())
    assert await kvp._reselect([1, 2, 3], frozenset()) == b.instance_id
    assert a.instance_id in seen["exclude"]
    await drt.close()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("k", [1, MAX_TOKENS - 1])
async def test_stream_identical_after_midstream_chaos_reclaim(seed, k):
    """A chaos-injected spot reclamation cuts the stream after K
    tokens; the journal continuation resumes on the survivor and the
    spliced stream is identical to an uninterrupted run, with the
    recovery attributed to ``reclaim``."""
    sched = ChaosSchedule(seed)
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client, seed)
    sched.reclaim_at(k, instance_id=a.instance_id, grace_s=1.0)

    before = get_telemetry().request_recoveries.labels("reclaim")._value.get()
    tokens, final = await collect_tokens(await router.generate(request_body()))

    assert tokens == expected_greedy()
    assert calls == ["a", "b"]
    assert final["finish_reason"] == "length"
    assert get_telemetry().request_recoveries.labels(
        "reclaim"
    )._value.get() == before + 1
    await drt.close()


# ---------------------------------------- engine: live migration transplant
PS = 8


@pytest.fixture(scope="module")
def engines():
    """Two identically-seeded engines: A is the spot instance the
    platform takes back, B the survivor. Same weights, so B's
    uninterrupted runs are the oracles for A's migrated streams."""
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import TINY
    from dynamo_exp_tpu.parallel import single_device_mesh

    def build():
        cfg = EngineConfig(
            model=TINY,
            max_decode_slots=4,
            page_size=PS,
            num_pages=64,
            max_model_len=128,
            eos_token_ids=[],
            kv_dtype="float32",
            kv_lease_ttl_s=0.25,  # the disagg-tuned TTL the clamp overrides
        )
        eng = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
        eng.start()
        return eng

    a, b = build(), build()
    yield a, b
    a.stop()
    b.stop()


async def run_engine(eng, token_ids, max_tokens, resume_offset=None,
                     request_id=None, **sampling):
    from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions
    from dynamo_exp_tpu.runtime.engine import AsyncEngineContext

    b = BackendInput(token_ids=list(token_ids))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    b.resume_offset = resume_offset
    if sampling:
        b.sampling_options = SamplingOptions(**sampling)
    ctx = AsyncEngineContext(request_id=request_id) if request_id else None
    stream = await eng.generate(b.to_dict(), ctx)
    tokens = []
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
    return tokens


async def _wait_inflight(eng, request_id, min_pages, timeout_s=20.0):
    """Poll the triage snapshot until the request is active with at
    least ``min_pages`` complete KV pages (bounded)."""
    for _ in range(int(timeout_s / 0.002)):
        for snap in await eng.reclaim_inflight():
            if (
                snap["request_id"] == request_id
                and snap["full_pages"] >= min_pages
            ):
                return snap
        await asyncio.sleep(0.002)
    pytest.fail(f"{request_id} never reached {min_pages} full pages in flight")


@contextlib.contextmanager
def _held_consume(eng, hold_s=0.01):
    """Hold every consume of ``eng`` a little. The tiny model ends a
    48-token stream a few milliseconds after its first pages fill, and
    a triage needs two round trips through the engine loop, each served
    once a window: without the hold, an event loop stalled here for a
    moment lets the stream end before the triage has seen it."""
    consume = eng._consume_ragged

    def held(pending):
        time.sleep(hold_s)
        consume(pending)

    eng._consume_ragged = held
    try:
        yield
    finally:
        del eng._consume_ragged


async def _migrate_inflight(eng_a, eng_b, prompt, n, rid, grace_s=10.0,
                            **sampling):
    """Start a stream on A, live-migrate it to B mid-flight over real
    TCP, let A finish. Returns (tokens, controller summary, sink)."""
    from dynamo_exp_tpu.disagg.transfer import KvPageReceiver

    receiver = KvPageReceiver()
    await receiver.start()
    sink = MigrationSink(eng_b, receiver)
    survivors = [
        SurvivorInfo(
            "eng-b",
            instance_id=2,
            topology=TopologyCoordinate(slice_id=0, host=0, chip=1),
            migrate_addr=receiver.address,
        )
    ]

    async def survivors_fn():
        return survivors

    ctl = ReclaimController(
        eng_a,
        instance="eng-a",
        topology=TopologyCoordinate(slice_id=0, host=0, chip=0),
        margin_s=0.05,
        survivors_fn=survivors_fn,
    )
    task = asyncio.ensure_future(
        run_engine(eng_a, prompt, n, request_id=rid, **sampling)
    )
    try:
        with _held_consume(eng_a):
            await _wait_inflight(eng_a, rid, min_pages=2)
            summary = await ctl.run(grace_s=grace_s)
            tokens = await asyncio.wait_for(task, 60)
        await sink.drain()
    finally:
        task.cancel()
        sink.close()
        await receiver.close()
    return tokens, summary, sink


async def test_live_migration_transplants_matchable_prefix(engines):
    """Tentpole acceptance: reclaim triage on a busy engine extracts
    the in-flight sequence's complete pages, ships them (chained block
    hashes on the BEGIN frame) over real TCP, and the survivor parks
    them as prefix blocks the journal continuation attaches
    content-addressed — saving the re-prefill without owning
    correctness."""
    eng_a, eng_b = engines
    prompt = [5, 9, 17, 23, 4, 31, 8, 2, 44, 6]
    n = 64

    tokens, summary, sink = await _migrate_inflight(
        eng_a, eng_b, prompt, n, rid="mig-greedy"
    )
    assert summary["planned"] >= 1
    assert summary["migrated"] >= 1
    assert summary["migrated_pages"] >= 2
    assert summary["deadline_degraded"] == 0
    assert sink.transfers >= 1 and sink.seeded_blocks >= 2

    # The migration changed nothing about A's stream (extraction reads
    # only complete pages; decode keeps writing positions >= pos).
    oracle = await run_engine(eng_b, prompt, n)
    assert tokens == oracle

    # The journal continuation on B attaches the transplanted prefix
    # (content-addressed via the recomputed hash chain) instead of
    # re-prefilling from page zero...
    shared_before = eng_b.metrics()["kv_prefix_hits_shared"]
    k = 32
    cont = await run_engine(eng_b, prompt + oracle[:k], n - k)
    assert eng_b.metrics()["kv_prefix_hits_shared"] > shared_before
    # ...and is token-identical to the uninterrupted oracle.
    assert cont == oracle[k:]


async def test_sink_drain_ends_when_injects_finished_before_their_callbacks():
    """``drain`` over injects that are all done already, their
    done-callbacks still queued on the loop, returns: ``gather`` then
    completes without yielding, so ``drain`` must not wait for the
    callbacks to empty its set (it used to spin there for ever)."""
    from types import SimpleNamespace

    sink = MigrationSink(None, SimpleNamespace(on_unclaimed=None))
    done = asyncio.get_running_loop().create_future()
    done.set_result(0)
    sink._tasks.add(done)
    done.add_done_callback(sink._tasks.discard)  # queued, not yet run
    await asyncio.wait_for(sink.drain(), 5)
    assert not sink._tasks


@pytest.mark.parametrize(
    "so",
    [
        {},  # greedy
        dict(temperature=0.9, top_p=0.9, seed=12345),  # seeded sampling
        dict(presence_penalty=5.0),  # penalty state across the splice
    ],
    ids=["greedy", "seeded", "penalized"],
)
async def test_migrated_stream_token_identical_across_sampling_modes(
    engines, so
):
    """Token identity is by construction (counter-based sampling keyed
    on absolute position), so it holds whether or not the migration
    landed — greedy, seeded, and penalized alike; the ``make chaos``
    lane re-runs this file with DYN_SPEC=ngram for the spec-on proof."""
    eng_a, eng_b = engines
    prompt = [7, 3, 19, 28, 41, 13]
    n, k = 48, 28
    rid = "mig-" + "-".join(map(str, sorted(so))) if so else "mig-plain"

    oracle = await run_engine(eng_b, prompt, n, **so)
    tokens, summary, _ = await _migrate_inflight(
        eng_a, eng_b, prompt, n, rid=rid, **so
    )
    assert tokens == oracle  # A's migrated-from stream, uninterrupted
    assert summary["migrated"] >= 1

    # The journal continuation on the survivor (prompt + confirmed
    # tokens re-prefilled; penalty counts rebuilt via resume_offset).
    cont = await run_engine(
        eng_b, prompt + oracle[:k], n - k,
        resume_offset=k if "presence_penalty" in so else None, **so
    )
    assert cont == oracle[k:]  # zero lost, zero duplicated


async def test_too_short_grace_falls_back_to_journal(engines):
    """A grace window under the safety margin migrates nothing: triage
    degrades every sequence to journal failover — and the continuation
    is still token-identical, proving migration is an optimization the
    correctness story never depends on."""
    eng_a, eng_b = engines
    prompt = [2, 4, 8, 16, 32, 64]
    n, k = 48, 16
    rid = "mig-short-grace"

    async def no_survivors():
        return [
            SurvivorInfo("eng-b", 2, migrate_addr="127.0.0.1:1")  # unused
        ]

    ctl = ReclaimController(
        eng_a, instance="eng-a", margin_s=1.0, survivors_fn=no_survivors
    )
    task = asyncio.ensure_future(run_engine(eng_a, prompt, n, request_id=rid))
    with _held_consume(eng_a):
        await _wait_inflight(eng_a, rid, min_pages=1)
        summary = await ctl.run(grace_s=0.2)  # budget = 0.2 - 1.0 < 0
    assert summary["planned"] >= 1
    assert summary["migrated"] == 0
    assert summary["failover"] == summary["planned"]
    tokens = await asyncio.wait_for(task, 60)

    oracle = await run_engine(eng_b, prompt, n)
    assert tokens == oracle
    cont = await run_engine(eng_b, prompt + oracle[:k], n - k)
    assert cont == oracle[k:]  # journal failover: nothing lost, nothing twice


async def test_reclaim_extract_leases_pages_until_confirmed(engines):
    """The extract pins pages under the clamped lease; the controller's
    confirm (ship done or failed) releases them — no stranded pins, no
    mid-transfer reap."""
    eng_a, _ = engines
    prompt = [9, 1, 9, 2, 9, 3]
    rid = "mig-lease"
    task = asyncio.ensure_future(
        run_engine(eng_a, prompt, 48, request_id=rid)
    )
    with _held_consume(eng_a):
        await _wait_inflight(eng_a, rid, min_pages=1)
        res = await eng_a.reclaim_extract(rid, ttl_s=30.0)
    assert res is not None
    hashes, pages, lease_id = res
    assert len(hashes) == len(pages) >= 1
    active_before = eng_a.metrics()["kv_leases_active"]
    assert active_before >= 1
    eng_a.confirm_kv_lease(lease_id)
    for _ in range(500):
        if eng_a.metrics()["kv_leases_active"] < active_before:
            break
        await asyncio.sleep(0.005)
    assert eng_a.metrics()["kv_leases_active"] < active_before
    await asyncio.wait_for(task, 60)
    # Unknown request: clean None, not an exception (the sequence may
    # finish between snapshot and extract during a real reclaim).
    assert await eng_a.reclaim_extract("no-such-request", 1.0) is None


# ------------------------------------------------------------ sim: economics
def _spot_sim(seed: int, **over):
    from dynamo_exp_tpu.sim.cluster import ClusterSim, SimConfig
    from dynamo_exp_tpu.sim.workload import ramp_workload

    cfg = SimConfig(
        seed=seed,
        slots_per_instance=8,
        pages_per_instance=144,
        page_size=16,
        max_inflight=16,
        shed_watermark=12,
        admission_per_instance=True,
        initial_instances=4,
        provision_s=5.0,
        reclaim_grace_s=4.0,
        **over,
    )
    wl = ramp_workload(
        seed,
        duration_s=240.0,
        rps_start=2.0,
        rps_end=8.0,
        prompt_len=(64, 256),
        max_tokens=(16, 64),
    )
    return ClusterSim(cfg, wl)


@pytest.mark.parametrize("seed", SEEDS)
def test_spot_fleet_goodput_near_ondemand_at_fraction_of_cost(seed):
    """Tentpole study: a 50%-spot fleet under seconds-scale grace and a
    steady reclaim schedule holds goodput near the all-on-demand
    control while the *billed* chip-seconds (spot time at
    spot_cost_factor) drop materially — live migration does the
    saving, journal failover does the surviving."""
    base = _spot_sim(seed).run()
    spot = _spot_sim(
        seed, spot_fraction=0.5, reclaim_rate_per_min=6.0
    ).run()

    assert spot.reclaims > 0, "scenario must actually reclaim instances"
    assert spot.reclaim_migrated > 0, "triage must land live migrations"
    assert spot.reclaim_migrated_pages > 0
    assert spot.completed + spot.shed + spot.errors == spot.submitted
    # Goodput within ~10% of the on-demand control (the hard floor
    # leaves slack for reclaim schedules whose respawn tail stretches
    # the measured drain window — e.g. seed 8 lands at 88%)...
    assert spot.goodput_tok_s >= 0.85 * base.goodput_tok_s
    # ...at materially fewer billed chip-seconds (the 50% spot share
    # bills at spot_cost_factor=0.3 → ≥ 20% under the control even
    # after respawn overhead).
    assert spot.billed_chip_seconds <= 0.8 * base.billed_chip_seconds
    assert base.billed_chip_seconds == pytest.approx(base.chip_seconds)


@pytest.mark.parametrize("seed", SEEDS)
def test_reclaim_event_log_bit_identical_per_seed(seed):
    """The reclaim schedule, triage plan, migration landings, and spot
    respawns are all on seeded streams: two runs of the same seed
    produce the same event log byte for byte and the same report."""
    s1 = _spot_sim(seed, spot_fraction=0.5, reclaim_rate_per_min=6.0)
    s2 = _spot_sim(seed, spot_fraction=0.5, reclaim_rate_per_min=6.0)
    r1, r2 = s1.run(), s2.run()
    assert any("reclaim notice" in e for e in s1.event_log)
    assert s1.event_log == s2.event_log
    d1, d2 = r1.to_dict(), r2.to_dict()
    assert "wall_clock_s" not in d1
    assert d1 == d2


# ------------------------------------------------------------- doc-sync
def test_reclaim_surface_is_documented():
    """Doc-sync guard (same contract as the fleet/anatomy planes): the
    operator surface and the suite row land with their documentation."""
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "docs", "fault_tolerance.md")) as f:
        ft = f.read()
    assert "Spot reclamation & live migration" in ft
    for needle in (
        "llmctl reclaim",
        "plan_triage",
        "migration_lease_ttl_s",
        "MigrationSink",
    ):
        assert needle in ft, f"{needle!r} undocumented in fault_tolerance.md"
    with open(os.path.join(root, "docs", "testing.md")) as f:
        testing = f.read()
    assert "tests/test_reclaim.py" in testing
    with open(os.path.join(root, "README.md")) as f:
        readme = f.read()
    assert "Spot reclamation" in readme
    with open(os.path.join(root, "Makefile")) as f:
        mk = f.read()
    assert "RECLAIM_SEED_SETS" in mk and "tests/test_reclaim.py" in mk
