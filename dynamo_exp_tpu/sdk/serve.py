"""Graph supervisor: one process per service worker, TPU chips allocated.

Reference parity: ``deploy/dynamo/sdk/cli/serving.py:58-187`` (circus
arbiter with one watcher per service, GPU allocation, per-watcher env) —
rebuilt on plain subprocesses with restart-with-backoff.

    python -m dynamo_exp_tpu.sdk.serve pkg.module:RootClass \
        [-f config.yaml] [--coordinator HOST:PORT | --start-coordinator] \
        [--service-name OnlyThisOne] [--tpu-chips N]
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import logging
import os
import signal
import sys
import time

logger = logging.getLogger("dynamo_exp_tpu.sdk.serve")

MAX_RESTARTS = 3
RESTART_WINDOW_S = 60.0


class Watcher:
    """One service worker process, restarted on unexpected death."""

    def __init__(self, spec, worker_idx: int, argv: list[str], env: dict[str, str]):
        self.spec = spec
        self.worker_idx = worker_idx
        self.argv = argv
        self.env = env
        self.proc: asyncio.subprocess.Process | None = None
        self.restarts: list[float] = []
        self.stopping = False

    @property
    def name(self) -> str:
        return f"{self.spec.name}[{self.worker_idx}]"

    async def start(self) -> None:
        self.proc = await asyncio.create_subprocess_exec(
            *self.argv, env={**os.environ, **self.env}
        )
        placement = " ".join(
            f"{k}={v}" for k, v in sorted(self.env.items())
            if k.startswith("TPU_") or k == "JAX_PLATFORMS"
        )
        logger.info(
            "started %s (pid %d) %s", self.name, self.proc.pid, placement
        )

    async def supervise(self) -> None:
        while not self.stopping:
            rc = await self.proc.wait()
            if self.stopping:
                return
            now = time.monotonic()
            self.restarts = [
                t for t in self.restarts if now - t < RESTART_WINDOW_S
            ] + [now]
            if len(self.restarts) > MAX_RESTARTS:
                raise RuntimeError(
                    f"{self.name} crashed {len(self.restarts)} times in "
                    f"{RESTART_WINDOW_S:.0f}s (rc={rc}); giving up"
                )
            logger.warning("%s exited rc=%s; restarting", self.name, rc)
            await asyncio.sleep(min(2 ** (len(self.restarts) - 1), 10))
            await self.start()

    async def stop(self, timeout: float = 20.0) -> None:
        self.stopping = True
        if self.proc is None or self.proc.returncode is not None:
            return
        self.proc.terminate()  # SIGTERM -> graceful drain in the child
        with contextlib.suppress(asyncio.TimeoutError):
            await asyncio.wait_for(self.proc.wait(), timeout)
        if self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()


class Supervisor:
    """Dynamic watcher set over a service graph.

    Beyond the static arbiter the reference builds on circus, this one
    takes live scale commands — the planner's LocalConnector drives
    ``add_worker``/``remove_worker`` through a control endpoint on the
    coordinator (reference parity:
    ``components/planner/src/dynamo/planner/circusd.py`` add/remove
    watchers via the circus control socket).
    """

    def __init__(
        self, target: str, graph, config, allocator, endpoint: str,
        multihost_argv: list[str] | None = None,
    ):
        self.target = target
        self.specs = {s.name: s for s in graph}
        self.config = config
        self.allocator = allocator
        self.endpoint = endpoint
        # Extra serve_service flags for the (single) TPU service when
        # this pod is one host of a multi-host slice.
        self.multihost_argv = multihost_argv
        self.watchers: dict[str, list[Watcher]] = {s.name: [] for s in graph}
        self._next_idx = {s.name: 0 for s in graph}
        self._tasks: dict[Watcher, asyncio.Task] = {}
        self.failed: asyncio.Future | None = None

    def _build_watcher(self, spec) -> Watcher:
        from .config import ENV_VAR

        env = {
            "DYN_RUNTIME_COORDINATOR_ENDPOINT": self.endpoint,
            ENV_VAR: self.config.dumps(),
            **self.allocator.assign(
                spec.name, int(spec.resources.get("tpu", 0))
            ),
        }
        argv = [
            sys.executable,
            "-m",
            "dynamo_exp_tpu.sdk.serve_service",
            self.target,
            "--service-name",
            spec.name,
        ]
        if self.multihost_argv and int(spec.resources.get("tpu", 0)) > 0:
            argv += self.multihost_argv
        idx = self._next_idx[spec.name]
        self._next_idx[spec.name] += 1
        return Watcher(spec, idx, argv, env)

    async def add_worker(self, service_name: str) -> bool:
        from .allocator import AllocationError

        spec = self.specs.get(service_name)
        if spec is None:
            return False
        try:
            w = self._build_watcher(spec)
        except AllocationError as e:
            logger.warning("add_worker(%s): %s", service_name, e)
            return False
        try:
            await w.start()
        except Exception:
            # Spawn failure must return the chips or repeated planner
            # retries would drain the budget permanently.
            self.allocator.release(w.env)
            logger.exception("add_worker(%s): spawn failed", service_name)
            return False
        self.watchers[service_name].append(w)
        self._tasks[w] = asyncio.ensure_future(self._supervise(w))
        return True

    async def remove_worker(self, service_name: str) -> bool:
        """Stop the newest worker of a service (SIGTERM → child drains,
        deregisters, lease-revokes on exit)."""
        ws = self.watchers.get(service_name) or []
        if not ws:
            return False
        w = ws.pop()
        task = self._tasks.pop(w, None)
        if task is not None:
            task.cancel()
        await w.stop()
        self.allocator.release(w.env)
        return True

    def counts(self) -> dict[str, int]:
        return {name: len(ws) for name, ws in self.watchers.items()}

    async def _supervise(self, w: Watcher) -> None:
        try:
            await w.supervise()
        except Exception as exc:  # crash-looped: surface to serve_graph
            if self.failed is not None and not self.failed.done():
                self.failed.set_exception(exc)

    def _initial_workers(self, spec) -> int:
        """YAML ``ServiceArgs: {workers: N}`` overrides the decorator's
        count (reference parity: per-service ServiceArgs in configs)."""
        svc_args = self.config.get(spec.name).get("ServiceArgs") or {}
        return int(svc_args.get("workers", spec.workers))

    async def start_initial(self) -> None:
        self.failed = asyncio.get_running_loop().create_future()
        for spec in self.specs.values():
            for _ in range(self._initial_workers(spec)):
                if not await self.add_worker(spec.name):
                    raise RuntimeError(f"failed to start {spec.name}")

    async def stop_all(self) -> None:
        for t in self._tasks.values():
            t.cancel()
        self._tasks.clear()
        await asyncio.gather(
            *[w.stop() for ws in self.watchers.values() for w in ws],
            return_exceptions=True,
        )

    async def serve_control(self, drt, namespace: str):
        """Control endpoint the planner's LocalConnector talks to:
        {"op": "add"|"remove"|"list", "service": name} → one reply frame
        {"ok": bool, "counts": {service: n}}."""

        async def handler(request: dict, context=None):
            op = request.get("op")
            service = request.get("service", "")
            ok = True
            if op == "add":
                ok = await self.add_worker(service)
            elif op == "remove":
                ok = await self.remove_worker(service)
            elif op != "list":
                ok = False
            yield {"data": {"ok": ok, "counts": self.counts()}}

        ep = drt.namespace(namespace).component("supervisor").endpoint("control")
        return await ep.serve_endpoint(handler)


async def serve_graph(args) -> None:
    from ..runtime.component import DistributedRuntime
    from ..runtime.config import RuntimeConfig
    from ..runtime.transports.coordinator import CoordinatorServer
    from .allocator import TPUAllocator
    from .config import ServiceConfig
    from .serve_service import load_target
    from .service import discover_graph

    root = load_target(args.target)
    graph = discover_graph(root)
    if args.service_name:
        graph = [s for s in graph if s.name == args.service_name]
        if not graph:
            raise SystemExit(f"no service named {args.service_name!r}")

    coordinator = None
    endpoint = args.coordinator
    if args.start_coordinator:
        coordinator = CoordinatorServer("127.0.0.1", args.coordinator_port)
        await coordinator.start()
        endpoint = coordinator.address
        print(f"coordinator on {endpoint}", flush=True)
    if not endpoint:
        raise SystemExit("need --coordinator or --start-coordinator")

    config = ServiceConfig.load(args.config)
    allocator = TPUAllocator(args.tpu_chips)
    multihost_argv: list[str] | None = None
    if getattr(args, "num_nodes", 1) > 1:
        # Multi-host slice: jax.distributed must be joined by the WORKER
        # process that owns the TPU (one process per host), not by this
        # supervisor — the flags are forwarded to the worker's
        # serve_service argv (deploy tier renders one pod per host rank
        # with these flags; reference capability: ray.rs:66-107).
        tpu_specs = [s for s in graph if int(s.resources.get("tpu", 0)) > 0]
        if len(tpu_specs) != 1 or tpu_specs[0].workers != 1:
            raise SystemExit(
                "--num-nodes > 1 needs --service-name selecting exactly one "
                "TPU service with workers=1 (one joined process per host)"
            )
        multihost_argv = [
            "--num-nodes", str(args.num_nodes),
            "--node-rank", str(args.node_rank),
            "--deployment", getattr(args, "deployment", "default"),
            "--dist-port", str(getattr(args, "dist_port", 9911)),
        ]
        if getattr(args, "dist_leader", ""):
            multihost_argv += ["--dist-leader", args.dist_leader]
    sup = Supervisor(
        args.target, graph, config, allocator, endpoint,
        multihost_argv=multihost_argv,
    )
    drt = DistributedRuntime(
        config=RuntimeConfig(coordinator_endpoint=endpoint)
    )
    control = None
    try:
        control = await sup.serve_control(drt, graph[0].namespace)
        await sup.start_initial()
        print(
            f"serving {sum(sup.counts().values())} workers: {sup.counts()}",
            flush=True,
        )
        await sup.failed  # runs until a watcher gives up or we're cancelled
    finally:
        await sup.stop_all()
        if control is not None:
            await control.close()
        await drt.close()
        if coordinator is not None:
            await coordinator.close()


def main(argv: list[str] | None = None) -> None:
    # DYN_LOG / DYN_LOGGING_JSONL aware (trace-correlated JSONL lines);
    # service processes inherit DYN_TRACE_FILE for span recording.
    from ..runtime.logging import configure_logging

    configure_logging()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("target", help="pkg.module:RootClass")
    p.add_argument("-f", "--config", default=None, help="service config YAML")
    p.add_argument("--coordinator", default=os.environ.get("DYN_COORDINATOR", ""))
    p.add_argument("--start-coordinator", action="store_true")
    p.add_argument("--coordinator-port", type=int, default=0)
    p.add_argument("--service-name", default=None, help="run one service only")
    p.add_argument("--tpu-chips", type=int, default=None,
                   help="host chip budget (default: $DYN_TPU_CHIPS, else "
                        "the TPU device nodes this host has)")
    p.add_argument("--num-nodes", type=int, default=1,
                   help="hosts in this service's TPU slice (multi-host)")
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--dist-leader", default="",
                   help="rank-0 host:port; empty = discover via coordinator")
    p.add_argument("--dist-port", type=int, default=9911,
                   help="port rank 0 binds for jax.distributed")
    p.add_argument("--deployment", default="default",
                   help="leader-key namespace for multi-host discovery")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])

    loop = asyncio.new_event_loop()
    task = loop.create_task(serve_graph(args))
    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError, ValueError):
            loop.add_signal_handler(sig, task.cancel)
    try:
        loop.run_until_complete(task)
    except asyncio.CancelledError:
        pass
    finally:
        loop.close()


if __name__ == "__main__":
    main()
