"""chip_smoke.py, checked where there is no chip.

The script itself must FAIL here (no TPU) — it has no CPU mode. Its
pieces are exercised against a CPU server this test starts itself at
``tiny`` width: the model-directory generator with a full-vocabulary
tokenizer, the server launcher, the request set and its response
checks, what the engine says about where it ran, and the log scan.
Also here, because they share the launcher: a dead engine loop flips
``/health`` and the exit code, and the compile-cache resolver.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke as cs
from dynamo_exp_tpu.models import TINY, ModelConfig
from dynamo_exp_tpu.models.synth import write_model_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# tiny widths, but room for the >1024-token prompt of the request set
# and for the byte alphabet + special tokens of the generated tokenizer.
SMOKE_TINY = dataclasses.replace(
    TINY, vocab_size=384, max_position_embeddings=2048
)


def _run_py_server(model_dir, log_path, prelude=""):
    """``run.py in=http out=tpu`` on the CPU through chip_smoke's own
    launcher; ``prelude`` runs in the child before ``main`` (fault
    injection)."""
    port = cs.free_port()
    code = (
        "import sys\n" + prelude
        + "from dynamo_exp_tpu.run import main\nmain(sys.argv[1:])\n"
    )
    argv = [
        sys.executable, "-c", code, "in=http", "out=tpu",
        "--model-path", model_dir, "--model-name", cs.MODEL_NAME,
        "--random-weights", "--http-host", "127.0.0.1",
        "--http-port", str(port), "--kv-dtype", "float32",
    ]
    server = cs.Server(argv, log_path, env={"JAX_PLATFORMS": "cpu"})
    base = f"http://127.0.0.1:{port}"
    server.wait_for(lambda: cs.is_healthy(base), "/health", 120)
    return server, base


# ------------------------------------------------------ the script itself
def test_script_fails_without_tpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=os.environ | {"JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert "holds no dynamo_exp_tpu package" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_require_tpu_env():
    cs.require_tpu_env({})
    cs.require_tpu_env({"JAX_PLATFORMS": "tpu"})
    cs.require_tpu_env({"JAX_PLATFORMS": "tpu,cpu"})
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.require_tpu_env({"JAX_PLATFORMS": "cpu"})


# ------------------------------------------------------- model directory
def test_generated_tokenizer_covers_the_whole_vocabulary(tmp_path):
    from dynamo_exp_tpu.tokenizer import Tokenizer

    path = write_model_dir(str(tmp_path / "m"), SMOKE_TINY, seed=3)
    assert ModelConfig.from_pretrained(path) == SMOKE_TINY
    tok = Tokenizer.from_pretrained(path)
    vocab = json.load(open(os.path.join(path, "tokenizer.json")))["model"]["vocab"]
    assert sorted(vocab.values()) == list(range(SMOKE_TINY.vocab_size))
    # Every id a random-weight model can sample goes through decode.
    for tid in range(SMOKE_TINY.vocab_size):
        assert isinstance(tok.decode([tid]), str)
    text = "hello <|user|> wörld"
    assert tok.decode(tok.encode(text).ids, skip_special_tokens=False) == text
    # Same seed, same directory; a vocabulary too small is refused.
    again = write_model_dir(str(tmp_path / "m2"), SMOKE_TINY, seed=3)
    assert (
        open(os.path.join(path, "tokenizer.json")).read()
        == open(os.path.join(again, "tokenizer.json")).read()
    )
    with pytest.raises(ValueError, match="byte alphabet"):
        write_model_dir(str(tmp_path / "m3"), TINY)


# ------------------------------------- launcher, request set, what it says
@pytest.fixture(scope="module")
def cpu_server(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("smoke")
    model_dir = write_model_dir(str(tmp / "model"), SMOKE_TINY)
    server, base = _run_py_server(model_dir, str(tmp / "server.log"))
    yield server, base
    server.stop()


def test_request_set_and_clean_exit_on_a_cpu_server(cpu_server):
    server, base = cpu_server
    recs = cs.run_request_set(base)
    assert len(recs) == 14
    assert all(r["tokens_returned"] == r["tokens_asked"] for r in recs)
    assert max(r["prompt_tokens"] for r in recs) > 1024

    status, metrics_text = cs.http_json(f"{base}/metrics")
    assert status == 200
    assert cs.ledger_violations(metrics_text) == 0
    info = cs.parse_build_info(metrics_text)
    assert (info["platform"], info["attn_impl"], info["device_count"]) == (
        "cpu", "xla", "1",
    )
    # What the chip run asserts is exactly what a CPU server is not.
    with pytest.raises(cs.SmokeFailure, match="platform.*attn_impl"):
        cs.check_build_info(info, 1)

    # A count that is not what was asked, and a status that is not 200.
    with pytest.raises(cs.SmokeFailure, match="asked 4000 tokens, got"):
        cs.chat(base, "too-long", "hi", 4000, stream=True)
    with pytest.raises(cs.SmokeFailure, match="HTTP 4"):
        cs.chat(base, "bad-request", "hi", 4, stream=False, priority="nope")
    with pytest.raises(cs.SmokeFailure, match="HTTP 4"):
        cs.chat(base, "bad-request", "hi", 4, stream=True, priority="nope")

    assert cs.is_healthy(base)
    assert server.stop() == 0
    log = server.log_text()
    cs.scan_log(log)
    assert "engine placed: platform=cpu" in log
    assert cs.pallas_variants(log) == set()  # xla programs only, here
    assert cs.peak_bytes(log) == [[None]]  # the CPU backend reports none
    with pytest.raises(cs.SmokeFailure, match="1 engine"):
        cs.check_peaks(cs.peak_bytes(log), engines=1, device_count=1)


def test_log_and_metrics_readers():
    with pytest.raises(cs.SmokeFailure, match="engine loop crashed"):
        cs.scan_log("INFO fine\nERROR engine loop crashed; failing\n")
    log = (
        "engine placed: platform=tpu device_ids=[0, 1] attn_impl=pallas "
        "peak_bytes_in_use=[5100, 5000]\n"
        "ragged variant: nb=1 pages=None windowed=True full_sampler=False "
        "want_lp=False with_spec=False attn=pallas\n"
        "ragged variant: nb=16 pages=64 windowed=False full_sampler=False "
        "want_lp=False with_spec=False attn=xla\n"
    )
    assert cs.pallas_variants(log) == {"windowed"}
    assert cs.peak_bytes(log) == [[5100, 5000]]
    cs.check_peaks([[5100, 5000]], engines=1, device_count=2)
    with pytest.raises(cs.SmokeFailure, match="not of one order"):
        cs.check_peaks([[9000, 5000]], engines=1, device_count=2)
    text = (
        'dynamo_kv_ledger_violations_total{kind="leak"} 2.0\n'
        'dynamo_build_info{platform="tpu",attn_impl="pallas-interpret",'
        'device_count="1"} 1.0\n'
    )
    assert cs.ledger_violations(text) == 2.0
    with pytest.raises(cs.SmokeFailure, match="pallas-interpret"):
        cs.check_build_info(cs.parse_build_info(text), 1)


# ------------------------------------------------------- dead engine loop
def test_dead_engine_loop_flips_health_and_exit_code(tmp_path):
    model_dir = write_model_dir(str(tmp_path / "model"), SMOKE_TINY)
    server, base = _run_py_server(
        model_dir, str(tmp_path / "server.log"),
        prelude=(
            "from dynamo_exp_tpu.engine import TPUEngine\n"
            "def boom(self, *a, **k):\n"
            "    raise RuntimeError('injected dispatch failure')\n"
            "TPUEngine._dispatch_ragged = boom\n"
        ),
    )
    try:
        assert cs.http_json(f"{base}/health")[0] == 200
        with pytest.raises(cs.SmokeFailure):
            cs.chat(base, "doomed", "hi", 4, stream=True)
        status, body = cs.http_json(f"{base}/health")
        assert status == 503
        assert body == {"status": "unhealthy", "reason": "engine loop crashed"}
    finally:
        rc = server.stop()
    assert rc not in (0, None)
    with pytest.raises(cs.SmokeFailure, match="engine loop crashed"):
        cs.scan_log(server.log_text())


# --------------------------------------------------- compile-cache resolver
_REPORT = (
    "import jax, json\n"
    "updates = []\n"
    "real = jax.config.update\n"
    "def spy(name, value):\n"
    "    updates.append([name, value])\n"
    "    return real(name, value)\n"
    "jax.config.update = spy\n"
    "from dynamo_exp_tpu.aot import enable_persistent_cache\n"
    "d = enable_persistent_cache({requested!r})\n"
    "print(json.dumps({{'dir': d, 'jax': jax.config.jax_compilation_cache_dir,"
    " 'updates': [v for n, v in updates if n == 'jax_compilation_cache_dir']}}))\n"
)


def _report(env, requested=""):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", _REPORT.format(requested=requested)],
        env=base | {"JAX_PLATFORMS": "cpu"} | env, cwd=REPO,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_dir_placed_from_outside(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the
    cache and nothing points JAX anywhere else — a flag included."""
    outside = str(tmp_path / "outside")
    runs = [
        _report({"JAX_COMPILATION_CACHE_DIR": outside}),
        _report({"JAX_COMPILATION_CACHE_DIR": outside},
                requested=str(tmp_path / "flag")),
    ]
    for r in runs:
        assert r == {"dir": outside, "jax": outside, "updates": []}


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    for r in (_report({}), _report({})):
        assert r == {"dir": fixed, "jax": fixed, "updates": [fixed]}
    flag = str(tmp_path / "flag")
    assert _report({}, requested=flag)["dir"] == flag


def test_fresh_cache_dir_is_fixed_emptied_and_refused_when_pinned(
    tmp_path, monkeypatch
):
    from dynamo_exp_tpu.aot import compile as aot_compile_mod

    monkeypatch.setattr(aot_compile_mod, "DEFAULT_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(
        aot_compile_mod, "enable_persistent_cache", lambda path: path
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = aot_compile_mod.fresh_cache_dir("aot-smoke")
    assert path == str(tmp_path / "aot-smoke")
    os.makedirs(path)
    open(os.path.join(path, "stale-entry"), "w").close()
    assert aot_compile_mod.fresh_cache_dir("aot-smoke") == path
    assert not os.path.exists(os.path.join(path, "stale-entry"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "pinned"))
    with pytest.raises(SystemExit, match="empty compile cache"):
        aot_compile_mod.fresh_cache_dir("aot-smoke")


def test_supervisor_parent_never_initialises_a_backend():
    """``sdk.serve`` imports the graph modules in the process that
    holds no chip: nothing on that path may bring a backend up."""
    code = (
        "import dynamo_exp_tpu.sdk.serve\n"
        "from dynamo_exp_tpu.sdk.serve_service import load_target\n"
        "from dynamo_exp_tpu.sdk.service import discover_graph\n"
        "g = discover_graph(load_target('examples.llm.graphs.agg:Frontend'))\n"
        "assert [s.name for s in g] == ['TpuWorker', 'Processor', 'Frontend']\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, timeout=120, check=True,
        env=os.environ | {"JAX_PLATFORMS": "cpu"},
    )
