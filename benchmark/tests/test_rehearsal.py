"""run_cell.py end to end on the CPU at a tiny configuration (a rehearsal
of the harness, never a measurement), and the ways it must refuse."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, trace, env=None, cwd=ROOT, script=None, seed=2**31 + 11, seconds=4):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored", **(env or {})}
    proc = subprocess.run(
        [sys.executable, script or os.path.join(BENCH, "run_cell.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=cwd, env=full, capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, lines


def result_of(proc, lines):
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(lines[-1])
    assert set(out) - {"breakdown"} == CONTRACT_KEYS
    return out


def reference_detail(proc) -> dict:
    """The ``reference`` part of the diagnostics line on stderr."""
    line = [l for l in proc.stderr.splitlines() if l.startswith("[bench] detail: ")][-1]
    return json.loads(line[len("[bench] detail: "):])["reference"]


def test_open_loop_rehearsal_prints_the_contract_line_and_no_device_metric():
    proc, lines = run_cell("tiny-qwen3.rehearsal-open", 0)
    out = result_of(proc, lines)
    # No ``comparison`` key in the configuration's file: the walk against
    # reference.py, two probes of eight steps, its tolerance, as ever.
    ref = reference_detail(proc)
    assert set(ref) == {"max_abs_logprob_diff", "tol", "steps", "why"}
    assert ref["steps"] == 16 and ref["tol"] == 0.1 and ref["max_abs_logprob_diff"] < 0.1
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 10
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert set(out["metrics"]) == {
        "tpot_p95_ms", "output_tok_s", "setup_s",
    }
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]


def test_traced_session_rehearsal_reads_counters_and_hits_the_prefix_cache():
    out = result_of(*run_cell("tiny-qwen3.rehearsal-sessions", 1, seconds=6))
    assert out["correct"] is True and out["attempted"] > 0
    m = out["metrics"]
    assert "setup_s" not in m and "tpot_p95_ms" not in m
    assert m["compiles_in_window"]["value"] == 0
    assert m["prefix_hit_page_share"]["value"] > 30
    assert m["kv_pool_occupancy_mean"]["value"] >= 0  # tiny and mostly idle
    # A CPU rehearsal prints no device metric.
    assert not {"device_idle_share", "attn_kernel_time_share", "hbm_peak_share"} & set(m)
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_fixed_population_session_rehearsal():
    out = result_of(*run_cell("tiny-qwen3.rehearsal-seats", 0, seconds=5))
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 10
    assert set(out["metrics"]) == {"tpot_p95_ms", "output_tok_s", "setup_s"}


def test_tp4_closed_loop_rehearsal_on_four_virtual_devices():
    proc, lines = run_cell(
        "tiny-moe.rehearsal-closed", 0,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["count"] == 4
    assert out["attempted"] >= 20 and out["failed"] == 0
    # Its configuration names ``routed_experts``: all three parts ran, on the mesh.
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [(0, 64), (0, 1024), (1, 64), (1, 1024)]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    assert [c["layer"] for c in ref["first_half"]] == [0, 1]
    assert 0 < ref["first_half_max_rel_err"] < ref["first_half_tol"]
    assert ref["median_abs_logprob_diff"] < ref["tol"] and ref["rows_near_tie"] > 0


@pytest.mark.parametrize("workload", ["qwen3-4b.chat-steady", "qwen3-4b.sessions-prefix"])
def test_a_cell_without_a_tpu_fails_and_prints_no_result(workload):
    proc, lines = run_cell(workload, 0)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in lines)
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        BENCH, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns(".work", "__pycache__", "tests"),
    )
    proc, lines = run_cell(
        "tiny-qwen3.rehearsal-open", 0, cwd=str(tmp_path),
        script=str(tmp_path / "benchmark" / "run_cell.py"), env={"PYTHONPATH": ""},
    )
    assert proc.returncode != 0 and not any(l.startswith("{") for l in lines)
