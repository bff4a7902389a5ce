"""dynlint (dynamo_exp_tpu/analysis/): per-rule fixture proofs, the
full-tree zero-unwaived-findings gate, waiver grammar, baseline flow,
and the rule/waiver doc-sync guards (docs/static_analysis.md)."""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from dynamo_exp_tpu.analysis import (
    RULES,
    WAIVER_TOKENS,
    DeterminismChecker,
    HostSyncChecker,
    LockManifest,
    RecompileHazardChecker,
    ThreadManifest,
    ThreadOwnershipChecker,
    VariantSiteManifest,
    Zone,
    lint_tree,
    parse_waivers,
)
from dynamo_exp_tpu.analysis.core import apply_waivers
from dynamo_exp_tpu.analysis.runner import main as lint_main

REPO = os.path.join(os.path.dirname(__file__), "..")

HOT = Zone("fix/hot.py")
DET = Zone("fix/seeded.py")


def run_checker(checker, path, src):
    """checker + waiver parse + waiver application (what lint_tree does
    per file), on dedented fixture source."""
    src = textwrap.dedent(src)
    findings = checker.check_source(path, src)
    waivers, waiver_findings = parse_waivers(path, src, WAIVER_TOKENS)
    apply_waivers(findings, waivers)
    return findings, waiver_findings


def unwaived(findings):
    return [f for f in findings if not f.waived]


# ------------------------------------------------------------- host-sync
def test_host_sync_fires_on_asarray_in_hot_zone():
    src = """
    import numpy as np

    def consume(pending):
        toks = np.asarray(pending.ys[0])
        return toks
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert [f.rule for f in findings] == ["host-sync"]
    assert "device→host sync" in findings[0].message


def test_host_sync_silent_on_clean_host_code():
    src = """
    import numpy as np

    def build(rows):
        tokens = np.zeros((rows, 4), np.int32)
        tokens[0, 0] = 7
        return int(tokens.shape[0])
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert findings == []


def test_host_sync_waived_with_reason():
    src = """
    import numpy as np

    def consume(pending):
        return np.asarray(pending.ys[0])  # dynlint: sync-point(test consume)
    """
    findings, wf = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert wf == []
    assert len(findings) == 1
    assert findings[0].waived and findings[0].reason == "test consume"


def test_host_sync_dataflow_device_vs_host():
    # jnp-derived names: truthiness and float() are syncs; names
    # materialized through np.asarray are host — int() over them is
    # bookkeeping, not a sync, so the allowlist stays true sync points.
    src = """
    import jax.numpy as jnp
    import numpy as np

    def bad(x):
        y = jnp.sum(x)
        if y:
            return float(y)

    def fine(pending):
        toks = np.asarray(pending.ys[0])  # dynlint: sync-point(test consume)
        return [int(t) for t in toks]
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    messages = sorted(f.message for f in unwaived(findings))
    assert len(messages) == 2
    assert "truthiness of a jax value" in messages[1]
    assert "float() of a jax value" in messages[0]


def test_host_sync_methods_flagged():
    src = """
    def peek(arr):
        return arr.item()

    def wait(arr):
        arr.block_until_ready()
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert sorted(f.message.split("(")[0] for f in findings) == [
        ".block_until_ready",
        ".item",
    ]


def test_host_sync_ignores_files_outside_zone():
    src = "import numpy as np\n\ntoks = np.asarray(object())\n"
    findings = HostSyncChecker(zones=(HOT,)).check_source("fix/cold.py", src)
    assert findings == []


# ----------------------------------------------------------- determinism
def test_determinism_fires_on_wall_clock_in_zone():
    src = """
    import time

    def stamp(ev):
        ev["t"] = time.time()
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert [f.rule for f in findings] == ["determinism"]
    assert "wall clock" in findings[0].message


def test_determinism_allows_seeded_rng_flags_unseeded():
    src = """
    import random
    import numpy as np

    def good(seed):
        rng = random.Random(seed)
        gen = np.random.default_rng(seed)
        return rng.random() + gen.random()

    def bad():
        return random.random() + np.random.randint(3) + hash("x")
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert len(findings) == 3
    assert all(f.line >= 10 for f in findings), findings


def test_determinism_waived_with_reason():
    src = """
    import time

    def wall():
        return time.perf_counter()  # dynlint: determinism(host-only timing)
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert len(findings) == 1 and findings[0].waived


def test_flight_payload_wall_time_regression():
    # The PR 8 gotcha as a rule: flight-ring payloads are compared
    # bit-for-bit across same-seed chaos runs; a wall time or uuid in a
    # payload breaks that the day it ships. Fires OUTSIDE the declared
    # determinism zones — payload sinks are checked tree-wide.
    src = """
    import time
    import uuid

    class Eng:
        def finish(self, seq):
            self.flight.record("finish", req=seq.rid, t_wall=time.time())

        def grant(self, pages):
            self.flight.record("lease_grant", lease=uuid.uuid4().hex)

        def clean(self, seq):
            self.flight.record("finish", req=seq.rid, generated=seq.n)
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "dynamo_exp_tpu/engine/fix.py", src
    )
    assert len(findings) == 2
    assert all("flight-recorder payload" in f.message for f in findings)
    assert {f.line for f in findings} == {7, 10}


# ------------------------------------------------------ thread-ownership
_FIX_MANIFEST = ThreadManifest(
    path="fix/eng.py",
    cls="Eng",
    loop_entries=("_loop",),
    external_entries=("stop", "submit"),
    loop_owned=frozenset({"_inflight", "_pending"}),
    handoff=frozenset({"_q"}),
)


def _ownership_checker():
    return ThreadOwnershipChecker(manifests=(_FIX_MANIFEST,), locks=())


def test_ownership_flags_external_write_to_loop_owned():
    src = """
    class Eng:
        def _loop(self):
            self._inflight = 1  # loop thread: fine

        def stop(self):
            self._inflight = None
    """
    findings, _ = run_checker(_ownership_checker(), "fix/eng.py", src)
    assert len(findings) == 1
    assert "stop" in findings[0].message and findings[0].line == 7


def test_ownership_flags_transitive_path_and_mutating_calls():
    src = """
    class Eng:
        def submit(self, x):
            self._q.put(x)  # handoff surface: fine
            self._bump()

        def _bump(self):
            self._pending.append(1)
    """
    findings, _ = run_checker(_ownership_checker(), "fix/eng.py", src)
    assert len(findings) == 1
    assert ".append()" in findings[0].message
    assert "submit" in findings[0].message


def test_ownership_waived_with_reason():
    src = """
    class Eng:
        def stop(self):
            self._inflight = None  # dynlint: thread-ownership(loop joined)
    """
    findings, _ = run_checker(_ownership_checker(), "fix/eng.py", src)
    assert len(findings) == 1 and findings[0].waived


def test_lock_guarded_access_outside_lock_flagged():
    lm = LockManifest(
        path="fix/pool.py",
        cls="Pool",
        lock="_lock",
        guarded=frozenset({"_data"}),
    )
    src = """
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._data = {}

        def good(self, k):
            with self._lock:
                return self._data.get(k)

        def bad(self, k):
            return self._data.get(k)
    """
    findings, _ = run_checker(
        ThreadOwnershipChecker(manifests=(), locks=(lm,)), "fix/pool.py", src
    )
    assert len(findings) == 1
    assert "outside `with self._lock:`" in findings[0].message
    assert findings[0].line == 14


# ------------------------------------------------------ recompile-hazard
_FIX_SITES = VariantSiteManifest(
    path="fix/eng.py", sites={"_decode_fn": (0, 1)}
)


def test_recompile_fires_on_unbucketed_variant_key():
    # The acceptance-criteria synthetic: a raw dynamic int in a
    # compiled-variant key position.
    src = """
    class Eng:
        def dispatch(self, part, cfg):
            return self._decode_fn(len(part), cfg.page_bucket_for(4))
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 1
    assert "arg 0" in findings[0].message
    assert "*_bucket_for" in findings[0].message


def test_recompile_silent_when_bucketed():
    src = """
    class Eng:
        def dispatch(self, part, cfg):
            rows = cfg.decode_rows_bucket_for(len(part))
            return self._decode_fn(rows, cfg.page_bucket_for(4))
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert findings == []


def test_recompile_waived_with_reason():
    src = """
    class Eng:
        def chained(self, pending, cfg):
            rows = pending.rows
            return self._decode_fn(rows, cfg.page_bucket_for(4))  # dynlint: recompile-hazard(carried bucket)
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 1 and findings[0].waived


@pytest.mark.parametrize(
    "sites,call,flagged",
    [
        # No keyword named: every keyword value must be bucketed.
        ((0,), "self._go(cfg.rows_bucket_for(n), live=n)", ["keyword 'live'"]),
        # The callee's shape-carrying keywords named: only those, and the
        # positions, are keys (``TPUEngine._launch``'s shape).
        ((0, "nb"), "self._go(cfg.rows_bucket_for(n), live=n)", []),
        ((0, "nb"), "self._go(n, live=n)", ["arg 0"]),
        ((0, "nb"), "self._go(nb=n, live=n)", ["keyword 'nb'"]),
        ((0, "nb"), "self._go(nb=cfg.rows_bucket_for(n), live=n)", []),
    ],
)
def test_recompile_named_keywords_limit_what_is_a_key(sites, call, flagged):
    src = f"""
    class Eng:
        def dispatch(self, n, cfg):
            return {call}
    """
    findings, _ = run_checker(
        RecompileHazardChecker(
            manifests=(VariantSiteManifest("fix/eng.py", {"_go": sites}),)
        ),
        "fix/eng.py", src,
    )
    assert [f.message.split(" of ")[0].split("key ")[1] for f in findings] == flagged


# --------------------------------------------------------- waiver grammar
def test_bare_waiver_without_reason_is_a_finding_and_waives_nothing():
    src = """
    import numpy as np

    def consume(pending):
        return np.asarray(pending.ys[0])  # dynlint: sync-point
    """
    findings, wf = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert unwaived(findings), "a bare waiver must not waive"
    assert len(wf) == 1 and "requires a reason" in wf[0].message


def test_unknown_waiver_token_is_a_finding():
    _, wf = run_checker(
        HostSyncChecker(zones=(HOT,)),
        "fix/hot.py",
        "x = 1  # dynlint: bogus(whatever)\n",
    )
    assert len(wf) == 1 and "unknown dynlint waiver token" in wf[0].message


def test_docstring_mention_is_not_a_waiver():
    src = '''
    def f():
        """Use # dynlint: sync-point(reason) to waive."""
        return 1
    '''
    waivers, wf = parse_waivers(
        "fix/hot.py", textwrap.dedent(src), WAIVER_TOKENS
    )
    assert waivers == {} and wf == []


def test_multiline_statement_waiver_covers_the_call():
    src = """
    import numpy as np

    def consume(pending):
        return np.asarray(  # dynlint: sync-point(spans lines)
            pending.ys[0]
        )
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 1 and findings[0].waived


# -------------------------------------------------- checker soundness
def test_host_sync_self_materialize_rebind_still_flagged():
    # `x = np.asarray(x)` on a jax value must not exempt itself: the
    # DEVICE classification is sticky against later host rebinds.
    src = """
    import jax.numpy as jnp
    import numpy as np

    def consume():
        ys = jnp.zeros(4)
        ys = np.asarray(ys)
        return ys
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 1 and "device→host sync" in findings[0].message


def test_host_sync_lambda_body_checked():
    src = """
    import numpy as np

    def install(dev):
        return lambda: np.asarray(dev)
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 1


def test_determinism_from_import_and_alias_flagged():
    src = """
    from time import time
    import random as rnd

    def stamp():
        return time(), rnd.random()
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert len(findings) == 2, findings


def test_recompile_rebind_kills_bucketed_name():
    # A bucketed name reassigned to a raw dynamic int must not launder
    # the value through its old classification.
    src = """
    class Eng:
        def dispatch(self, part, cfg):
            rows = cfg.decode_rows_bucket_for(len(part))
            rows = len(part)
            return self._decode_fn(rows, cfg.page_bucket_for(4))
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 1 and "arg 0" in findings[0].message


def test_recompile_use_before_bucketed_rebind_still_flagged():
    # Use sites consult the binding state AT their line: a bucketed
    # rebind after a raw dispatch must not retroactively whitewash it.
    src = """
    class Eng:
        def dispatch(self, part, cfg):
            rows = len(part)
            fn = self._decode_fn(rows, cfg.page_bucket_for(4))
            rows = cfg.decode_rows_bucket_for(len(part))
            return fn, rows
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 1 and "arg 0" in findings[0].message


def test_baseline_is_a_multiset_of_identical_lines(tmp_path, capsys):
    # Baselining one occurrence of a line must not suppress a NEW,
    # textually identical occurrence added later.
    root = _write_fixture_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    assert lint_main(["--root", str(root), "--baseline", bl,
                      "--update-baseline"]) == 0
    capsys.readouterr()
    bad = tmp_path / "dynamo_exp_tpu" / "sim" / "bad.py"
    bad.write_text(
        bad.read_text()
        + "\n\ndef stamp_again():\n    return time.time()\n"
    )
    assert lint_main(["--root", str(root), "--baseline", bl]) == 1


def test_waiver_on_any_line_of_enclosing_statement(tmp_path):
    # The documented contract: a waiver anywhere on the multi-line
    # statement covers a finding on an inner line.
    pkg = tmp_path / "dynamo_exp_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "offload.py").write_text(
        textwrap.dedent(
            """
            import numpy as np

            def consume(pending):
                out = np.clip(  # dynlint: sync-point(fixture waiver)
                    np.asarray(pending.ys[0]),
                    0,
                    9,
                )
                return out
            """
        )
    )
    findings = lint_tree(str(tmp_path))
    assert findings and all(f.waived for f in findings), findings


def test_unused_waiver_is_reported(tmp_path):
    pkg = tmp_path / "dynamo_exp_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "offload.py").write_text(
        "def f():\n    return 1  # dynlint: sync-point(stale entry)\n"
    )
    findings = lint_tree(str(tmp_path))
    assert len(findings) == 1
    assert findings[0].rule == "waiver-syntax"
    assert "unused waiver" in findings[0].message
    # ...but not under --rule filtering, where other rules' waivers are
    # legitimately unmatched.
    assert lint_tree(str(tmp_path), rules=["determinism"]) == []


def test_host_sync_comparison_on_device_value_flagged():
    # `if n > 0:` blocks exactly like `if n:` — the comparison idiom
    # must not slip past the truthiness check.
    src = """
    import jax.numpy as jnp

    def wait(mask):
        n = jnp.sum(mask)
        if n > 0:
            return 1
        while 0 < n and n < 9:
            n = n - 1
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 3, findings
    assert all("comparison" in f.message for f in findings)


def test_waiver_in_if_body_does_not_waive_the_if_test(tmp_path):
    # A compound statement's span is its HEADER: a waiver inside the
    # block body must not silently cover a finding on the `if` test.
    pkg = tmp_path / "dynamo_exp_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "offload.py").write_text(
        textwrap.dedent(
            """
            import jax.numpy as jnp
            import numpy as np

            def consume(pending, mask):
                done = jnp.all(mask)
                if done:
                    toks = np.asarray(pending)  # dynlint: sync-point(inner waiver)
                    return toks
            """
        )
    )
    findings = lint_tree(str(tmp_path))
    bad = unwaived(findings)
    assert len(bad) == 1 and "truthiness" in bad[0].message, findings


def test_recompile_nested_def_does_not_launder_outer_scope():
    src = """
    class Eng:
        def dispatch(self, part, cfg):
            rows = len(part)

            def helper():
                rows = cfg.decode_rows_bucket_for(8)
                return rows

            return self._decode_fn(rows, cfg.page_bucket_for(4))
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 1 and "arg 0" in findings[0].message


def _tiny_engine():
    from dynamo_exp_tpu.engine.config import EngineConfig
    from dynamo_exp_tpu.engine.engine import TPUEngine
    from dynamo_exp_tpu.models import TINY
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=TINY, max_decode_slots=2, page_size=4, num_pages=16,
        max_model_len=64, eos_token_ids=[], kv_dtype="float32",
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


def test_generate_fails_fast_when_engine_cannot_start():
    # A wedged previous loop makes start() refuse; generate() must
    # raise instead of enqueueing work nothing will ever consume.
    import asyncio
    import threading

    eng = _tiny_engine()
    gate = threading.Event()
    t = threading.Thread(target=gate.wait, daemon=True)
    t.start()
    eng._thread = t  # simulate the wedged loop surviving stop()

    async def go():
        await eng.generate({"token_ids": [1, 2]})

    try:
        try:
            asyncio.run(go())
        except RuntimeError as e:
            assert "not running" in str(e)
        else:
            raise AssertionError("generate() should have raised")
        assert eng._submit_q.empty()
    finally:
        gate.set()
        t.join()
        eng._thread = None


def test_start_clears_stale_state_from_wedged_then_exited_loop():
    # The timed-out stop() skipped teardown; once the wedged loop
    # eventually exits, the next start() must not resurrect its
    # in-flight window or buffered evictions.
    import threading

    eng = _tiny_engine()
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()  # dead thread standing in for the unwedged-then-exited loop
    eng._thread = t
    eng._inflight = object()
    eng._pending_offloads.append((0, 1))
    eng.start()
    try:
        assert eng._running
        assert eng._inflight is None
        assert eng._pending_offloads == []
    finally:
        eng.stop()


def test_engine_start_refuses_second_loop_while_thread_alive():
    # Companion of the stop()-timeout fix: a wedged loop surviving a
    # timed-out join must not be joined by a second loop thread.
    import threading

    from dynamo_exp_tpu.engine.engine import TPUEngine

    eng = TPUEngine.__new__(TPUEngine)  # no device work needed
    eng._running = False
    alive = threading.Event()
    t = threading.Thread(target=alive.wait, daemon=True)
    t.start()
    eng._thread = t
    try:
        eng.start()
        assert eng._running is False and eng._thread is t
    finally:
        alive.set()
        t.join()


def test_host_sync_device_attribute_casts_flagged():
    # Persistent device state is recognized by attribute name: a
    # truthiness/cast on `self._counts`/`pending.tokens_dev` is a sync
    # even though no local dataflow ever classified it.
    src = """
    class Eng:
        def probe(self, slot, pending):
            if self._counts[slot] > 0:
                return int(pending.tokens_dev[0])
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 2, findings


def test_determinism_allows_default_rng_seed_kwarg():
    src = """
    import numpy as np

    def gen(cfg):
        return np.random.default_rng(seed=cfg.seed).random()
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert findings == []


def test_ownership_loop_entry_body_never_flagged():
    # A loop-entry method's writes are the sanctioned loop mutations,
    # even when an external entry's call graph reaches it.
    src = """
    class Eng:
        def _loop(self):
            self._inflight = 1

        def stop(self):
            self._loop()
    """
    findings, _ = run_checker(_ownership_checker(), "fix/eng.py", src)
    assert findings == []


def test_cli_normalizes_explicit_paths(capsys):
    # Absolute and ./-prefixed paths must resolve to the declared
    # repo-relative zone form (waivers recognized, checkers applied).
    target = "dynamo_exp_tpu/engine/offload.py"
    for spec in (
        target,
        "./" + target,
        os.path.abspath(os.path.join(REPO, target)),
    ):
        rc = lint_main(["--json", "--root", REPO, spec])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["counts"]["unwaived"] == 0, (spec, out)
        assert out["counts"]["waived"] >= 2, spec  # the CopyStream syncs


def test_recompile_lambda_and_keyword_sites_checked():
    src = """
    class Eng:
        def install(self, part, cfg):
            cb = lambda: self._decode_fn(len(part), 1)
            kw = self._decode_fn(rows=len(part))
            return cb, kw
    """
    findings, _ = run_checker(
        RecompileHazardChecker(manifests=(_FIX_SITES,)), "fix/eng.py", src
    )
    assert len(findings) == 2, findings
    assert any("keyword 'rows'" in f.message for f in findings)


def test_determinism_submodule_and_aliased_from_imports():
    src = """
    from numpy.random import default_rng
    from datetime import datetime as dt

    def gen():
        return default_rng(), dt.now()
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert len(findings) == 2, findings


def test_host_sync_methods_on_proven_host_values_not_flagged():
    src = """
    import numpy as np

    def consume(pending):
        h = np.asarray(pending.ys[0])  # dynlint: sync-point(test consume)
        return h.tolist(), np.asarray(kw=pending.ys[1])
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    # .tolist() on the materialized host copy is bookkeeping; the
    # keyword-arg conversion is still a (second, unwaived) sync.
    assert len(findings) == 2, findings
    assert len(unwaived(findings)) == 1
    assert "np.asarray" in unwaived(findings)[0].message


def test_flight_payload_taint_through_local_flagged():
    # The laundered spelling of the PR 8 gotcha: the wall clock lands
    # in a local first, then rides into the payload.
    src = """
    import time

    class Eng:
        def stall(self, seq):
            now = time.perf_counter()
            self.flight.record("stall_start", req=seq.rid, at=now)
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "dynamo_exp_tpu/engine/fix.py", src
    )
    assert len(findings) == 1
    assert "via local 'now'" in findings[0].message


def test_host_sync_ternary_assert_comprehension_truthiness_flagged():
    src = """
    import jax.numpy as jnp

    def probe(mask):
        x = jnp.sum(mask)
        assert x
        y = 1 if x else 2
        return [i for i in range(3) if x], y
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 3, findings
    assert all("truthiness" in f.message for f in findings)


def test_host_sync_device_method_results_propagate():
    # `x.any()` / `x.sum()` on a device value yield device values: a
    # cast or truthiness over them is a sync.
    src = """
    import jax.numpy as jnp

    def probe(mask):
        x = jnp.zeros(4)
        if x.any():
            return int(x.sum())
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert len(findings) == 2, findings


def test_determinism_unseeded_random_instance_flagged():
    src = """
    import random

    def gen():
        return random.Random().random()
    """
    findings, _ = run_checker(
        DeterminismChecker(zones=(DET,)), "fix/seeded.py", src
    )
    assert len(findings) == 1
    assert "unseeded random.Random()" in findings[0].message


def test_is_none_identity_check_on_device_value_not_flagged():
    src = """
    class Eng:
        def ensure(self):
            if self.k_cache is None:
                return 1
            if self.k_cache is not None and self.v_cache is None:
                return 2
    """
    findings, _ = run_checker(HostSyncChecker(zones=(HOT,)), "fix/hot.py", src)
    assert findings == []


def test_zone_exclude_is_path_qualified():
    # exclude=("Eng.generate",) exempts the method itself, but NOT a
    # nested helper that happens to reuse the name inside loop code.
    zone = Zone("fix/hot.py", exclude=("Eng.generate",))
    src = """
    import numpy as np

    class Eng:
        def generate(self, pending):
            return np.asarray(pending.ys[0])  # excluded submission path

        def _loop(self, pending):
            def generate():
                return np.asarray(pending.ys[0])  # NOT exempt

            return generate()
    """
    findings, _ = run_checker(HostSyncChecker(zones=(zone,)), "fix/hot.py", src)
    assert len(findings) == 1 and findings[0].line == 10, findings


def test_update_baseline_requires_baseline(capsys):
    assert lint_main(["--root", REPO, "--update-baseline"]) == 2
    assert "--baseline" in capsys.readouterr().err


# ------------------------------------------------------- full-tree gate
def test_full_tree_zero_unwaived_findings():
    """THE tier-1 gate: the shipped tree is clean — every finding of
    every rule is inline-waived with a reason. A new implicit sync, a
    wall clock in a seeded zone, a cross-thread write, or a raw variant
    key fails this test at diff time."""
    findings = lint_tree(REPO)
    bad = unwaived(findings)
    assert not bad, "unwaived dynlint findings:\n" + "\n".join(
        f"{f.file}:{f.line}: {f.rule}: {f.message}" for f in bad
    )
    for f in findings:
        assert f.reason, f"waiver without reason at {f.file}:{f.line}"


def test_documented_engine_sync_points_are_the_allowlist():
    """Satellite guard: the documented engine sync points (the ragged
    dispatch consumes, the extract gather, the CopyStream transfer)
    are exactly the kind of entries the host-sync allowlist holds —
    and they all carry reasons."""
    findings = [
        f for f in lint_tree(REPO, rules=["host-sync"]) if f.waived
    ]
    reasons = {f.reason for f in findings}
    assert {
        "ragged consume",
        "extract gather consume",
        "offload copy-thread transfer",
    } <= reasons, reasons
    files = {f.file for f in findings}
    assert "dynamo_exp_tpu/engine/engine.py" in files
    assert "dynamo_exp_tpu/engine/offload.py" in files


# ------------------------------------------------------------- doc-sync
def _static_analysis_doc() -> str:
    with open(os.path.join(REPO, "docs", "static_analysis.md")) as f:
        return f.read()


def test_every_rule_name_is_documented():
    """Doc-sync guard (same registry-walk shape as the telemetry
    metric doc-sync): every dynlint rule must appear in
    docs/static_analysis.md — new rules land with their docs."""
    doc = _static_analysis_doc()
    missing = [r for r in RULES if f"`{r}`" not in doc]
    assert not missing, f"rules undocumented in static_analysis.md: {missing}"
    # Waiver tokens are part of the documented grammar too.
    missing = [t for t in WAIVER_TOKENS if f"`{t}`" not in doc]
    assert not missing, f"waiver tokens undocumented: {missing}"


def test_every_waiver_reason_is_documented():
    """The allowlist and the doc cannot drift: every inline waiver
    reason used in the tree must appear verbatim in the allowlist
    table of docs/static_analysis.md."""
    doc = _static_analysis_doc()
    reasons = {f.reason for f in lint_tree(REPO) if f.waived}
    missing = sorted(r for r in reasons if r not in doc)
    assert not missing, (
        f"waiver reasons not documented in static_analysis.md: {missing}"
    )


# ------------------------------------------------------------------ CLI
def test_cli_json_clean_tree(capsys):
    rc = lint_main(["--json", "--root", REPO])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counts"]["unwaived"] == 0
    assert out["counts"]["waived"] > 0
    for f in out["waived"]:
        assert f["rule"] in RULES and f["reason"]


def _write_fixture_tree(tmp_path):
    pkg = tmp_path / "dynamo_exp_tpu" / "sim"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\n\ndef stamp():\n    return time.time()\n"
    )
    return tmp_path


def test_cli_rule_filter_and_exit_codes(tmp_path, capsys):
    root = str(_write_fixture_tree(tmp_path))
    assert lint_main(["--root", root]) == 1  # determinism finding
    capsys.readouterr()
    assert lint_main(["--root", root, "--rule", "host-sync"]) == 0


def test_cli_baseline_roundtrip(tmp_path, capsys):
    """--baseline: incremental adoption — snapshot today's findings,
    then only NEW findings fail the run."""
    root = str(_write_fixture_tree(tmp_path))
    bl = str(tmp_path / "dynlint_baseline.json")
    assert (
        lint_main(["--root", root, "--baseline", bl, "--update-baseline"])
        == 0
    )
    capsys.readouterr()
    assert lint_main(["--root", root, "--baseline", bl]) == 0
    # A new violation is NOT covered by the old baseline.
    (tmp_path / "dynamo_exp_tpu" / "sim" / "worse.py").write_text(
        "import uuid\n\n\ndef rid():\n    return uuid.uuid4().hex\n"
    )
    capsys.readouterr()
    assert lint_main(["--root", root, "--baseline", bl]) == 1


def test_llmctl_lint_plane():
    """`llmctl lint` is the operator spelling of the same runner."""
    import asyncio

    from dynamo_exp_tpu.llmctl import build_parser, run

    args = build_parser().parse_args(["lint", "--json", "--root", REPO])
    assert args.plane == "lint"
    assert asyncio.run(run(args)) == 0
