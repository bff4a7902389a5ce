"""AOT variant precompilation + warm-boot provisioning (docs/aot.md).

Four layers of proof, all on the CPU mesh:

1. **Manifest determinism** — same config → byte-identical manifest
   JSON and hash, in-process and across processes (the hash IS the
   cache-invalidation key); lattice enumeration covers every value the
   live ``*_bucket_for`` helpers can emit.
2. **Warm boot** — a second engine booted from a populated persistent
   compilation cache compiles ZERO new variants: no ragged compile
   misses under traffic, no variant growth past the prewarmed set, no
   new cache entries — and the profiler's freshness state is seeded so
   prewarmed move kernels are never mis-charged as cold compiles.
3. **Identity** — greedy / seeded / penalized / spec-on streams are
   token-identical between a prewarmed engine and a cold one (prewarm
   executes all-padding batches; nothing it computes can reach an
   emitted token).
4. **The provisioning study** — feeding ``plan_step_slo`` the warm
   ``provision_s`` absorbs the same diurnal burst with fewer
   chip-seconds AND better SLO attainment than the cold one, and
   ``sim/fit.py`` learns warm-vs-cold ``provision_s`` from tagged
   coldstart bench lines.
"""

import asyncio
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dynamo_exp_tpu.aot import (
    build_manifest,
    manifest_for_engine,
    mixed_token_buckets,
    page_bound_buckets,
    page_move_buckets,
    resolve_ragged_key,
    windowed_token_buckets,
)
from dynamo_exp_tpu.aot.lattice import CompileManifest, impl_for_key
from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions
from dynamo_exp_tpu.telemetry.dispatch import DispatchProfiler

PS = 8


def _cfg(**over) -> EngineConfig:
    base = dict(
        model=TINY,
        max_decode_slots=2,
        page_size=PS,
        num_pages=64,
        max_model_len=128,
        prefill_chunk=16,
        decode_window=4,
        eos_token_ids=[],
        kv_dtype="float32",
    )
    return EngineConfig(**(base | over))


def _engine(**over) -> TPUEngine:
    return TPUEngine(_cfg(**over), mesh=single_device_mesh(), seed=0)


async def _collect(engine, prompt, max_tokens=8, seed=None, **sampling):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    if sampling or seed is not None:
        b.sampling_options = SamplingOptions(seed=seed, **sampling)
    stream = await engine.generate(b.to_dict())
    toks = []
    async for item in stream:
        toks.extend(item.get("token_ids", []))
    return toks


# ------------------------------------------------------------ determinism
def _manifest(cfg=None) -> CompileManifest:
    return build_manifest(
        cfg or _cfg(),
        attn_impl="xla",
        mesh_shape={"tp": 1, "sp": 1},
        jax_version="test",
    )


def test_manifest_hash_deterministic_in_process():
    a, b = _manifest(), _manifest()
    assert a.to_json() == b.to_json()
    assert a.hash() == b.hash()
    # JSON round-trip preserves the hash (what `llmctl aot list` /
    # warm-boot hash checks compare).
    assert CompileManifest.from_json(a.to_json()).hash() == a.hash()


def test_manifest_hash_moves_with_lattice_inputs():
    base = _manifest().hash()
    assert _manifest(_cfg(max_decode_slots=4)).hash() != base
    assert _manifest(_cfg(num_pages=128)).hash() != base
    assert _manifest(_cfg(decode_window=8)).hash() != base
    assert _manifest(_cfg(spec_mode="ngram")).hash() != base


def test_manifest_hash_identical_across_processes(tmp_path):
    """The acceptance bit: same config → byte-identical hash in a
    DIFFERENT interpreter (no id()/hash()/dict-order leakage)."""
    script = (
        "import json\n"
        "from dynamo_exp_tpu.aot import build_manifest\n"
        "from dynamo_exp_tpu.engine import EngineConfig\n"
        "from dynamo_exp_tpu.models import TINY\n"
        "cfg = EngineConfig(model=TINY, max_decode_slots=2, page_size=8,\n"
        "                   num_pages=64, max_model_len=128,\n"
        "                   prefill_chunk=16, decode_window=4,\n"
        "                   eos_token_ids=[], kv_dtype='float32')\n"
        "m = build_manifest(cfg, attn_impl='xla',\n"
        "                   mesh_shape={'tp': 1, 'sp': 1},\n"
        "                   jax_version='test')\n"
        "print(m.hash())\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        check=True,
        env=os.environ | {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "42"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.stdout.decode().strip().splitlines()[-1] == _manifest().hash()


def test_bucket_enumeration_covers_live_helpers():
    """Every value a ``*_bucket_for`` helper can return for a legal
    input appears in the enumerated bucket set — the lattice has no
    blind spots the live loop could dispatch into."""
    cfg = _cfg(max_decode_slots=8, max_model_len=256)
    wb = set(windowed_token_buckets(cfg))
    for n in range(1, cfg.max_decode_slots + 1):
        assert cfg.ragged_tokens_bucket_for(n) in wb
    mb = set(mixed_token_buckets(cfg))
    for n in range(1, cfg.ragged_max_tokens + 1, 7):
        assert cfg.ragged_tokens_bucket_for(n, mixed=True) in mb
    pb = set(page_bound_buckets(cfg))
    for p in range(1, cfg.max_pages_per_seq + 1):
        assert cfg.ragged_page_bucket_for(p) in pb
    # Move buckets must cover cross-sequence eviction bursts too: one
    # _flush_offloads sweep can gather up to the whole pool, not just
    # one sequence's pages.
    mv = set(page_move_buckets(cfg))
    for p in range(1, max(cfg.num_pages, cfg.max_pages_per_seq) + 1, 3):
        assert cfg.page_move_bucket_for(p) in mv


# A shape with a page axis to lose: page buckets of 128, 256 and 512
# pages (1024, 2048, 4096 tokens), window row buckets 1, 2, 4, 8.
_DEEP = dict(max_decode_slots=8, max_model_len=4096, num_pages=1024)


@pytest.mark.parametrize(
    "attention_impl, resolved",
    [("auto", "pallas"), ("pallas", "pallas"), ("auto", "xla"), ("xla", "xla")],
)
@pytest.mark.parametrize("windowed", [True, False], ids=["window", "mixed"])
@pytest.mark.parametrize(
    "nb, pages", [(1, 128), (16, 128), (32, 128), (64, 512)]
)
def test_resolve_ragged_key_form_is_the_resolved_one(
    attention_impl, resolved, windowed, nb, pages
):
    """The key's form is the engine's resolved implementation and
    nothing else: on the kernel no key holds a page bound — a window of
    1 row or of 64 over the smallest page bucket (where ``auto`` sent it
    down the gather until PR 30's table, PERF.md §6) as little as a
    mixed dispatch — and on the gather every key holds its bucket.
    ``auto`` adds nothing to what ``resolve_attn_impl`` decided, so it
    can override neither an explicit ``pallas`` nor an explicit
    ``xla``."""
    cfg = _cfg(attention_impl=attention_impl, **_DEEP)
    key = resolve_ragged_key(cfg, resolved, nb, pages, windowed, False, False)
    assert key == (
        nb, None if resolved == "pallas" else pages, windowed,
        False, False, False,
    )
    assert impl_for_key(key) == resolved


@pytest.mark.parametrize(
    "attn_impl, windowed, mixed", [("pallas", 16, 20), ("xla", 48, 60)]
)
def test_manifest_lattice_counts(attn_impl, windowed, mixed):
    """The lattice in numbers at ``_DEEP``: 4 window row buckets and 5
    mixed token buckets (16 .. 256), each x 2 samplers x 2
    log-probability forms; the gather multiplies both by its 3 page
    buckets, the kernel by none (before PR 30 its windows kept the
    1024-token bucket beside the unbounded key: 32 of them)."""
    cfg = _cfg(**_DEEP)
    manifest = build_manifest(
        cfg, attn_impl=attn_impl, mesh_shape={"tp": 1, "sp": 1},
        jax_version="test",
    )
    got = [v.windowed for v in manifest.ragged]
    assert (sum(got), len(got) - sum(got)) == (windowed, mixed)
    bounds = {v.pages for v in manifest.ragged}
    assert bounds == ({None} if attn_impl == "pallas" else {128, 256, 512})


@pytest.mark.parametrize("slots", [8, 64])
def test_a_mixed_variant_is_keyed_by_its_token_bucket_alone(slots):
    """Packing a mixed stream's decode rows (PR 32) added no axis: where
    the packed tail starts is ``EngineConfig.ragged_packed_slots(nb)``,
    so a kernel engine's lattice has one mixed variant a token bucket,
    sampler and log-probability form as before — the count
    ``benchmark/run_cell.py::warm_lattice`` builds and ``setup_s`` pays
    for — and every bucket ``_mixed_bucket_for`` can choose is in it."""
    import dataclasses

    cfg = _cfg(max_decode_slots=slots, max_model_len=4096, num_pages=1152)
    manifest = build_manifest(
        cfg, attn_impl="pallas", mesh_shape={"tp": 1}, jax_version="test",
        include_lp=False, include_spec=False,
    )
    mixed = [v for v in manifest.ragged if not v.windowed]
    buckets = mixed_token_buckets(cfg)
    assert len(mixed) == 2 * len(buckets)  # greedy and the full sampler
    assert sorted({v.nb for v in mixed}) == buckets
    assert [f.name for f in dataclasses.fields(mixed[0])] == [
        "nb", "pages", "windowed", "full_sampler", "want_lp", "with_spec"]
    eng = _engine(max_decode_slots=slots, pallas_interpret=True)
    for rows in range(0, slots + 1, max(1, slots // 8)):
        for aligned in (0, 8, 64, 160, 304, 512, 1024):
            assert eng._mixed_bucket_for(aligned, rows) in buckets


def test_resolved_key_matches_live_ragged_fn():
    """The engine's ``_ragged_fn`` and the offline ``resolve_ragged_key``
    are literally the same keying rule (one computes through the
    other) — a dispatch lands in ``_ragged_fns`` under the key the
    lattice predicts."""
    eng = _engine()
    key = resolve_ragged_key(
        eng.cfg, eng._attn_impl, 2, 4, True, False, False
    )
    eng._ragged_fn(2, 4, True, False, False)
    assert key in eng._ragged_fns


# --------------------------------------------------------------- warm boot
def test_warm_boot_compiles_nothing(tmp_path):
    """Two boots against one persistent cache dir: the first populates
    (prewarm executes + serializes every variant), the second
    deserializes — zero ragged compile misses under traffic, zero
    variant growth past the prewarmed set, zero new cache entries, and
    the move-kernel freshness state seeded (satellite: prewarm must
    never be mis-charged as a cold compile)."""
    from dynamo_exp_tpu.aot import enable_persistent_cache

    cache = enable_persistent_cache(str(tmp_path / "cache"))

    def boot():
        eng = _engine()
        manifest = manifest_for_engine(eng)
        report = eng.prewarm(manifest)
        toks_g = asyncio.run(_collect(eng, range(20, 36)))
        toks_s = asyncio.run(
            _collect(eng, range(20, 36), seed=5, temperature=0.8)
        )
        m = eng.metrics()
        eng.stop()
        return eng, manifest, report, m, (toks_g, toks_s)

    eng1, manifest, rep1, m1, toks1 = boot()
    assert rep1.ragged_variants == len(manifest.ragged)
    assert m1["prewarmed_variants"] == rep1.variants > 0
    assert m1["prewarm_seconds"] > 0
    files1 = len(os.listdir(cache))
    assert files1 > 0, "persistent cache serialized nothing"

    eng2, _, rep2, m2, toks2 = boot()
    # Zero compiles on second boot's traffic: the misses counter stays
    # flat from the very first dispatch...
    assert m2["dispatch"]["ragged"]["compile_misses"] == 0
    assert m2["dispatch"]["ragged"]["compile_total_s"] == 0.0
    # ...traffic never grows the cache past the prewarmed lattice...
    assert m2["compiled_ragged_variants"] == len(manifest.ragged)
    assert m2["compiled_ragged_variants"] == m1["compiled_ragged_variants"]
    # ...and the persistent cache gained nothing (every executable the
    # second boot needed was already serialized).
    assert len(os.listdir(cache)) == files1
    # Prewarm seeded the move-kernel freshness state: a prewarmed
    # bucket's first live dispatch must not read as a fresh compile.
    for bucket in manifest.move_buckets:
        assert not eng2.profiler.first_variant("gather", bucket)
        assert not eng2.profiler.first_variant("scatter", bucket)
    assert not eng2.profiler.first_variant("cow", 0)
    # Same streams both boots (and prewarm left no residue).
    assert toks1 == toks2


def test_the_persistent_cache_does_not_outlive_the_test_that_enabled_it():
    """``test_warm_boot_compiles_nothing`` points JAX's process-wide
    cache options at its ``tmp_path``. The root conftest puts them back
    when a test ends, so no later test of this worker compiles through
    (and deserialises executables from) a directory of an earlier one:
    where a worker died in ``test_recompile_guard_steady_state``."""
    import jax

    assert jax.config.jax_compilation_cache_dir == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    )
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_prewarm_refuses_running_engine():
    eng = _engine()
    eng.start()
    try:
        with pytest.raises(RuntimeError, match="before the engine"):
            eng.prewarm()
    finally:
        eng.stop()


def test_profiler_seed_variants_suppresses_first_variant():
    prof = DispatchProfiler()
    prof.seed_variants("gather", (8, 16))
    assert not prof.first_variant("gather", 8)
    assert not prof.first_variant("gather", 16)
    assert prof.first_variant("gather", 32)  # unseeded keys still fresh


# ---------------------------------------------------------------- identity
def test_identity_prewarmed_vs_cold_all_sampler_modes():
    """Greedy / seeded / penalized / spec-on streams are token-identical
    between a prewarmed engine and a cold one: prewarm's all-padding
    batches write no KV and touch no live penalty row, so the first
    real request sees exactly a cold engine's state."""
    over = dict(spec_mode="ngram", spec_draft_len=3, spec_adaptive=False)
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(3, 200, size=8 + 3 * i)) for i in range(3)]
    block = [50, 51, 52, 53, 54, 55, 56, 57]
    reqs = [
        (prompts[0], {}),
        (prompts[1], {"seed": 7, "temperature": 0.8, "top_k": 20}),
        (
            prompts[2],
            {
                "seed": 11,
                "temperature": 0.7,
                "frequency_penalty": 0.4,
                "presence_penalty": 0.2,
                "repetition_penalty": 1.2,
            },
        ),
        (block * 4, {}),  # repetitive: the n-gram drafter engages
    ]

    def streams(prewarmed: bool):
        eng = _engine(**over)
        if prewarmed:
            eng.prewarm()
        out = [
            asyncio.run(_collect(eng, p, 10, **kw)) for p, kw in reqs
        ]
        spec = eng.spec_dispatches
        eng.stop()
        return out, spec

    warm, warm_spec = streams(True)
    cold, cold_spec = streams(False)
    assert warm == cold
    assert warm_spec > 0 and cold_spec > 0  # speculation actually ran


# --------------------------------------------------- provisioning study
@pytest.mark.sim
def test_diurnal_burst_warm_provision_fewer_chip_seconds():
    """The ROADMAP acceptance: with the measured warm ``provision_s``,
    ``plan_step_slo`` absorbs the same diurnal burst with FEWER
    chip-seconds than the cold baseline while meeting the SLOs at
    least as well — scale-up lands on the burst's rising edge instead
    of being bought in advance as standby capacity."""
    from dynamo_exp_tpu.planner import PlannerConfig, SloTargets
    from dynamo_exp_tpu.sim import (
        ClusterSim,
        ServiceTimeModel,
        SimConfig,
        diurnal_workload,
    )

    def run(provision_s: float):
        workload = diurnal_workload(
            7, duration_s=900.0, rps_base=0.5, rps_peak=12.0,
            period_s=300.0,
        )
        cfg = SimConfig(
            seed=7,
            slots_per_instance=8,
            pages_per_instance=256,
            page_size=16,
            max_inflight=64,
            admission_per_instance=True,
            initial_instances=1,
            provision_s=provision_s,
            planner="slo",
            planner_cfg=PlannerConfig(max_tpu_budget=16, min_endpoint=1),
            slo=SloTargets(
                ttft_p99_slo_s=2.0,
                itl_p99_slo_s=0.2,
                provision_s=provision_s,
            ),
            service=ServiceTimeModel.default(),
            record_events=False,
        )
        return ClusterSim(cfg, workload).run()

    cold = run(120.0)  # cold boot: first traffic pays the lattice
    warm = run(8.0)  # warm boot from a populated compile cache
    assert warm.chip_seconds < cold.chip_seconds, (
        warm.chip_seconds, cold.chip_seconds,
    )
    assert warm.goodput_requests >= cold.goodput_requests
    assert warm.slo_violations_ttft <= cold.slo_violations_ttft
    # Deterministic per seed (the sim suite's standing rule).
    again = run(8.0)
    assert again.chip_seconds == warm.chip_seconds
    assert again.goodput_requests == warm.goodput_requests


def test_fit_learns_warm_provision_from_tagged_bench_lines(tmp_path):
    """``sim/fit.py`` splits coldstart samples by their ``prewarmed``
    tag: warm samples win (the fleet plans with its warm landing
    delay); cold-only files fall back to the cold samples."""
    from dynamo_exp_tpu.sim.fit import ServiceTimeModel

    def line(arm, prov, prewarmed):
        return {
            "metric": f"coldstart_tiny_isl64_osl16_c2_{arm}",
            "value": prov,
            "provision_s": prov,
            "prewarmed": prewarmed,
            "manifest_hash": "abc",
        }

    both = tmp_path / "bench.json"
    both.write_text(
        json.dumps(line("cold", 120.0, False))
        + "\n"
        + json.dumps(line("warm", 8.0, True))
        + "\n"
    )
    model = ServiceTimeModel.from_bench_json([both])
    assert model.provision_s == 8.0
    assert model.planner_hints()["provision_s"] == 8.0

    cold_only = tmp_path / "cold.json"
    cold_only.write_text(json.dumps(line("cold", 120.0, False)) + "\n")
    assert ServiceTimeModel.from_bench_json([cold_only]).provision_s == 120.0


# --------------------------------------------------------------------- CLI
def test_llmctl_aot_list_prints_manifest(capsys):
    from dynamo_exp_tpu.llmctl import main as llmctl_main

    rc = llmctl_main(
        [
            "aot", "list", "--preset", "tiny", "--max-decode-slots", "2",
            "--page-size", "8", "--max-model-len", "128",
            "--prefill-chunk", "16", "--kv-dtype", "float32",
        ]
    )
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    manifest = CompileManifest.from_dict(doc)
    assert manifest.ragged and manifest.move_buckets
    assert manifest.engine["max_decode_slots"] == 2
