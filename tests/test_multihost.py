"""Multi-host bring-up tests.

Reference capability anchors: ``lib/llm/src/engines.rs:41-50``
(MultiNodeConfig), ``lib/engines/vllm0_7/src/ray.rs:66-107`` (leader /
follower join), ``launch/dynamo-run/src/net.rs`` (leader address
detection). TPU-native: ``jax.distributed`` forms the global runtime;
the 2-process e2e forms an 8-device global mesh from two 4-device CPU
processes and runs one sharded step on it.
"""

import asyncio
import os
import socket
import subprocess
import sys

from dynamo_exp_tpu.parallel import MultiNodeConfig, resolve_leader_addr
from .fixtures import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



# ------------------------------------------------------------------- config
def test_multinode_config_roles():
    assert not MultiNodeConfig().is_multi_node
    cfg = MultiNodeConfig(num_nodes=2, node_rank=1)
    assert cfg.is_multi_node and not cfg.is_leader
    assert MultiNodeConfig(num_nodes=2, node_rank=0).is_leader


async def test_leader_publish_and_discover():
    """Rank 0 publishes its address in the control-plane KV; a follower
    reads it back (the reference's head/worker handshake)."""
    from dynamo_exp_tpu.runtime.component import DistributedRuntime
    from dynamo_exp_tpu.runtime.config import RuntimeConfig
    from dynamo_exp_tpu.runtime.transports.coordinator import CoordinatorServer

    server = CoordinatorServer()
    await server.start()
    drt = DistributedRuntime(
        config=RuntimeConfig(coordinator_endpoint=server.address)
    )
    try:
        leader = MultiNodeConfig(num_nodes=2, node_rank=0, dist_port=7707)
        addr = await resolve_leader_addr(leader, drt.discovery)
        assert addr.endswith(":7707")
        follower = MultiNodeConfig(num_nodes=2, node_rank=1)
        got = await resolve_leader_addr(follower, drt.discovery, timeout_s=5)
        assert got == addr
    finally:
        await drt.close()
        await server.close()


async def test_follower_without_discovery_rejected():
    import pytest

    with pytest.raises(ValueError, match="follower needs"):
        await resolve_leader_addr(MultiNodeConfig(num_nodes=2, node_rank=1))


# ---------------------------------------------------------------------- e2e
_CHILD = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
rank = int(sys.argv[1]); port = sys.argv[2]

from dynamo_exp_tpu.parallel import MultiNodeConfig, initialize_multihost
cfg = MultiNodeConfig(num_nodes=2, node_rank=rank,
                      leader_addr=f"127.0.0.1:{port}")
initialize_multihost(cfg)

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from dynamo_exp_tpu.parallel import build_mesh

assert jax.device_count() == 8, jax.device_count()
assert jax.process_count() == 2
mesh = build_mesh(dp=2, tp=4)

# One sharded step over the GLOBAL mesh: batch split over dp (one half
# per host), weight columns over tp; psum-style reduction via matmul.
x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
w = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8) / 100.0
xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
ws = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))

@jax.jit
def step(x, w):
    return jnp.tanh(x @ w).sum()

got = float(step(xs, ws))
want = float(np.tanh(np.asarray(x) @ np.asarray(w)).sum())
assert abs(got - want) < 1e-4, (got, want)
print(f"rank {rank} ok: {got:.4f}", flush=True)
"""


async def test_two_process_global_mesh_sharded_step():
    """Two 4-device CPU processes join one jax.distributed runtime,
    build a global dp=2 x tp=4 mesh, and agree on a sharded result."""
    port = free_port()
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    procs = [
        await asyncio.create_subprocess_exec(
            sys.executable, "-c", _CHILD, str(rank), str(port),
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for rank in (0, 1)
    ]
    outs = await asyncio.wait_for(
        asyncio.gather(*[p.communicate() for p in procs]), timeout=180
    )
    for rank, (p, (out, _)) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out.decode()}"
        assert f"rank {rank} ok" in out.decode()
