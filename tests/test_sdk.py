"""SDK tests: decorators, graph discovery, config merge, allocator, and
the hello_world 3-process e2e through the real supervisor.

Reference capability anchors: ``deploy/dynamo/sdk`` tests
(``test_config.py``, ``test_link.py``, ``test_e2e.py`` with the toy
pipeline fixture) and ``examples/hello_world``.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from dynamo_exp_tpu.sdk import ServiceConfig, depends, endpoint, get_spec, service
from dynamo_exp_tpu.sdk.allocator import AllocationError, TPUAllocator
from dynamo_exp_tpu.sdk.service import discover_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- decorators
def test_service_spec_and_graph_discovery():
    from examples.hello_world.hello_world import Backend, Frontend, Middle

    spec = get_spec(Frontend)
    assert spec.namespace == "hello"
    assert "generate" in spec.endpoints
    names = [s.name for s in discover_graph(Frontend)]
    # Dependencies first, root last.
    assert names == ["Backend", "Middle", "Frontend"]
    assert get_spec(Middle).cls is Middle
    assert get_spec(Backend).workers == 1


def test_endpoint_decorator_forms():
    @service()
    class S:
        @endpoint
        async def bare(self, request):
            yield {}

        @endpoint("named")
        async def other(self, request):
            yield {}

    spec = get_spec(S)
    assert set(spec.endpoints) == {"bare", "named"}


def test_depends_unresolved_raises():
    from examples.hello_world.hello_world import Middle

    with pytest.raises(RuntimeError, match="not resolved"):
        _ = Middle().backend


# -------------------------------------------------------------------- config
def test_service_config_yaml_env_merge(tmp_path, monkeypatch):
    cfg = tmp_path / "c.yaml"
    cfg.write_text("Frontend:\n  greeting: hi\n  depth: 1\nMiddle:\n  x: 2\n")
    monkeypatch.setenv(
        "DYN_SERVICE_CONFIG", json.dumps({"Frontend": {"depth": 9}})
    )
    sc = ServiceConfig.load(str(cfg))
    assert sc.get("Frontend") == {"greeting": "hi", "depth": 9}  # env wins
    assert sc.get("Middle") == {"x": 2}
    assert sc.get("Nope") == {}

    class Obj:
        pass

    o = Obj()
    sc.apply_to(o, "Frontend")
    assert o.greeting == "hi" and o.depth == 9


# ----------------------------------------------------------------- allocator
def test_tpu_allocator_assigns_disjoint_chips():
    alloc = TPUAllocator(total_chips=2)
    a = alloc.assign("decode", 1)
    b = alloc.assign("prefill", 1)
    # One chip per process, and libtpu told the process is a whole
    # one-chip slice of its own (TPU_VISIBLE_CHIPS alone fails every
    # process after the first on the chip host).
    for env, chip in ((a, "0"), (b, "1")):
        assert env == {
            "TPU_VISIBLE_CHIPS": chip,
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
    with pytest.raises(AllocationError):
        alloc.assign("extra", 1)
    with pytest.raises(AllocationError, match="one chip per worker"):
        alloc.assign("wide", 2)
    alloc.release(a)
    assert alloc.assign("again", 1)["TPU_VISIBLE_CHIPS"] == "0"
    # Host-side services stay off the TPU.
    assert alloc.assign("frontend", 0) == {"JAX_PLATFORMS": "cpu"}


def test_host_chip_budget_is_the_hosts_not_a_constant(monkeypatch):
    from dynamo_exp_tpu.sdk import allocator

    monkeypatch.setenv("DYN_TPU_CHIPS", "3")
    assert TPUAllocator().total_chips == 3
    monkeypatch.delenv("DYN_TPU_CHIPS")
    monkeypatch.setattr(
        allocator.glob, "glob", lambda pat: ["/dev/vfio/0", "/dev/vfio/1"]
    )
    assert TPUAllocator().total_chips == 2
    monkeypatch.setattr(allocator.glob, "glob", lambda pat: [])
    assert TPUAllocator().total_chips == 0  # no chips: none to hand out


# ----------------------------------------------------------------------- e2e
async def test_hello_world_graph_end_to_end():
    """Real supervisor, three service processes, request through the
    full Frontend->Middle->Backend chain, config override applied."""
    from dynamo_exp_tpu.runtime.component import DistributedRuntime
    from dynamo_exp_tpu.runtime.config import RuntimeConfig
    from dynamo_exp_tpu.runtime.transports.coordinator import CoordinatorServer

    server = CoordinatorServer()
    await server.start()
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        DYN_SERVICE_CONFIG=json.dumps({"Frontend": {"greeting": "bonjour"}}),
    )
    sup = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_exp_tpu.sdk.serve",
        "examples.hello_world.hello_world:Frontend",
        "--coordinator", server.address,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    drt = DistributedRuntime(
        config=RuntimeConfig(coordinator_endpoint=server.address)
    )
    try:
        ep = drt.namespace("hello").component("Frontend").endpoint("generate")
        client = await ep.client()
        for _ in range(200):
            if client.instances or sup.returncode is not None:
                break
            await asyncio.sleep(0.1)
        if not client.instances:
            out = b""
            if sup.returncode is not None:
                out = await sup.stdout.read()
            raise AssertionError(
                f"Frontend never came up (sup rc={sup.returncode}):\n"
                + out.decode()
            )

        from dynamo_exp_tpu.runtime.push_router import PushRouter

        router = PushRouter(client)
        stream = await router.generate({"text": "world"})
        tokens = [item["token"] async for item in stream]
        assert tokens == ["bonjour", "world-mid-back"]
    finally:
        sup.terminate()
        try:
            await asyncio.wait_for(sup.wait(), 30)
        except asyncio.TimeoutError:
            sup.kill()
        await drt.close()
        await server.close()
