"""Fleet chaos drills over the REAL Kafka wire (ISSUE 9 satellite).

The drain/stale/shed/kill drills in tests/test_chaos.py run on
``InMemoryMesh``; this file runs the same scenario shapes against the
in-repo ``kafkad`` broker through ``KafkaWireMesh`` — per-replica broker
connections (the true multi-process fleet shape), real consumer groups,
real compacted-table reads for the registry, CI's kafka-wire lane.

Stamps still ride the ``cancellation.wall_clock`` seam, so replica
staleness stays deterministic under the virtual clock even with a real
broker in the loop; only delivery latency is real.
"""

import asyncio

import pytest

from calfkit_tpu import protocol
from calfkit_tpu.client import Client
from calfkit_tpu.client.caller import RetryPolicy
from calfkit_tpu.exceptions import EngineOverloadedError
from calfkit_tpu.fleet import FailoverPolicy, FleetRouter
from calfkit_tpu.mesh.kafka_wire import (
    KafkaWireMesh,
    find_kafkad,
    spawn_kafkad,
)

from tests._chaos import (
    FleetTopology,
    ServingStubModel,
    settle,
    virtual_clock,
)

pytestmark = pytest.mark.skipif(
    find_kafkad() is None, reason="kafkad not built (make -C native)"
)

# real-broker deliveries take ms, not µs: give the bounded waits room
SETTLE = dict(ticks=1200, interval=0.01)


@pytest.fixture(scope="module")
def broker_port():
    proc = spawn_kafkad(0)
    yield proc.kafkad_port
    proc.terminate()
    proc.wait(timeout=5)


def _fleet(broker_port, models, **kw):
    """FleetTopology with one REAL broker connection per replica (each
    worker owns and stops its own)."""
    meshes = [
        KafkaWireMesh(f"127.0.0.1:{broker_port}") for _ in models
    ]
    return FleetTopology(meshes[0], models, meshes=meshes, **kw)


async def _routable(router, n):
    await router.start()
    await settle(
        lambda: len(router.registry.eligible("svc")) == n,
        message="fleet never became routable over the wire",
        **SETTLE,
    )


class TestFleetSoakOverKafka:
    async def test_drain_handoff(self, broker_port):
        """Drain one of two replicas: every subsequent call lands on the
        other, over real consumer groups and replica-addressed topics."""
        with virtual_clock():
            models = [ServingStubModel(text=f"r{i}") for i in range(2)]
            client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            await client_mesh.start()
            fleet = _fleet(broker_port, models)
            async with fleet:
                router = FleetRouter(
                    client_mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(client_mesh, router=router)
                await _routable(router, 2)
                low = fleet.index_of_lowest_key()
                first = await client.agent("svc").execute("warm", timeout=60)
                assert first.output == f"r{low}"
                fleet.workers[low].drain()
                await settle(
                    lambda: [
                        r.instance_id
                        for r in router.registry.eligible("svc")
                    ] == [fleet.instance_id(1 - low)],
                    message="drain never reached the registry",
                    **SETTLE,
                )
                for i in range(3):
                    result = await client.agent("svc").execute(
                        f"post-drain {i}", timeout=60
                    )
                    assert result.output == f"r{1 - low}"
                assert fleet.calls_delivered(low) == 1
                assert fleet.calls_delivered(1 - low) == 3
                await client.close()
            await client_mesh.stop()

    async def test_stale_exclusion_and_recovery(self, broker_port):
        """A wedged heartbeat goes stale under the virtual clock and the
        replica stops drawing traffic; one re-advert restores it."""
        with virtual_clock() as clock:
            models = [ServingStubModel(text=f"r{i}") for i in range(2)]
            client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            await client_mesh.start()
            fleet = _fleet(broker_port, models)
            async with fleet:
                router = FleetRouter(
                    client_mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(client_mesh, router=router)
                await _routable(router, 2)
                low = fleet.index_of_lowest_key()
                fleet.wedge_heartbeat(low)
                clock.advance(fleet.config.stale_after + 1)
                await settle(
                    lambda: [
                        r.instance_id
                        for r in router.registry.eligible("svc")
                    ] == [fleet.instance_id(1 - low)],
                    message="the wedged replica never went stale",
                    **SETTLE,
                )
                result = await client.agent("svc").execute(
                    "while-stale", timeout=60
                )
                assert result.output == f"r{1 - low}"
                await fleet.resume_heartbeat(low)
                await settle(
                    lambda: len(router.registry.eligible("svc")) == 2,
                    message="re-advert did not restore eligibility",
                    **SETTLE,
                )
                result = await client.agent("svc").execute("back", timeout=60)
                assert result.output == f"r{low}"
                await client.close()
            await client_mesh.stop()

    async def test_shed_retry_storm(self, broker_port):
        """Typed sheds from one replica are retried on the OTHER, with
        the shed source excluded — over the real wire, where the fault
        record's x-mesh-error-type has to round-trip the broker."""
        with virtual_clock():
            models = [ServingStubModel(text=f"r{i}") for i in range(2)]
            client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            await client_mesh.start()
            fleet = _fleet(broker_port, models)
            async with fleet:
                low = fleet.index_of_lowest_key()

                async def shed(messages, settings=None, params=None):
                    raise EngineOverloadedError(
                        "synthetic shed", lane="short", pending=9, limit=1
                    )

                models[low].request = shed
                router = FleetRouter(
                    client_mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(client_mesh, router=router)
                await _routable(router, 2)
                results = await asyncio.gather(*[
                    client.agent("svc").execute(
                        f"storm {i}", timeout=120,
                        retry=RetryPolicy(attempts=3, base_delay=0.01),
                    )
                    for i in range(4)
                ])
                assert all(r.output == f"r{1 - low}" for r in results)
                # every run touched the shedder at most once; every
                # retry landed on the survivor
                assert fleet.calls_delivered(1 - low) == 4
                await client.close()
            await client_mesh.stop()

    async def test_kill_mid_run_fails_over(self, broker_port):
        """The new ISSUE 9 drill on the real wire: hard-kill the placed
        replica mid-run; the supervised call re-dispatches to the
        survivor under the remaining deadline and completes."""

        class BlockedStubModel(ServingStubModel):
            def __init__(self, **kw):
                super().__init__(**kw)
                self.release = asyncio.Event()

            async def request(self, messages, settings=None, params=None):
                await self.release.wait()
                return await super().request(messages, settings, params)

        with virtual_clock() as clock:
            models = [BlockedStubModel(text=f"r{i}") for i in range(2)]
            client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            await client_mesh.start()
            fleet = _fleet(broker_port, models)
            async with fleet:
                low = fleet.index_of_lowest_key()
                models[1 - low].release.set()  # only the victim blocks
                router = FleetRouter(
                    client_mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(
                    client_mesh, router=router,
                    failover=FailoverPolicy(
                        probe_interval=0.05, max_failovers=2
                    ),
                )
                await _routable(router, 2)
                call = asyncio.create_task(
                    client.agent("svc").execute("kill me", timeout=120)
                )
                await settle(
                    lambda: fleet.calls_delivered(low) == 1,
                    message="the call never reached the victim",
                    **SETTLE,
                )
                fleet.kill(low)
                clock.advance(fleet.config.stale_after + 1)
                result = await call
                assert result.output == f"r{1 - low}"
                assert fleet.calls_delivered(1 - low) == 1
                assert fleet.agents[1 - low]._failover_requests == 1
                models[low].release.set()  # clean teardown
                await client.close()
            await client_mesh.stop()


class TestOrphanReapOverKafka:
    async def test_orphan_reap_soak(self, broker_port):
        """Orphan-reap soak over the REAL wire (ISSUE 10): a LEASED
        client fire-and-forgets runs into a REAL engine through kafkad —
        beats on the real compacted ``mesh.caller_liveness`` table, the
        worker's liveness feed folding them back — then dies hard (beat
        task killed, no tombstone).  One virtual TTL later the engine
        has reaped every orphan: drained, zero leaked slots/pages,
        ORPHANS counted."""
        import time as _time

        jax = pytest.importorskip("jax")
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from calfkit_tpu.inference import model as M
        from calfkit_tpu.inference.client import JaxLocalModelClient
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine
        from calfkit_tpu.nodes import Agent
        from calfkit_tpu.worker import Worker

        from tests._chaos import assert_engine_drained

        cfg = preset("debug")
        params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        runtime = RuntimeConfig(
            max_batch_size=4, max_seq_len=128, prefill_chunk=16,
            decode_steps_per_dispatch=1, page_size=16, kv_layout="paged",
        )
        engine = InferenceEngine(cfg, runtime, params=params)
        total_free = engine._page_alloc.free_pages

        def pace(point):
            if point == "dispatch":
                _time.sleep(0.01)

        engine._chaos = pace
        model = JaxLocalModelClient(
            config=cfg, runtime=runtime, engine=engine, max_new_tokens=100
        )
        with virtual_clock() as clock:
            worker_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
            await client_mesh.start()
            agent = Agent("leased", model=model)
            # lanes are crc32(task key) % max_workers and serial within a
            # lane: at the default 8, two of the three sends share a lane one
            # run in three, the second waits out the first's whole turn, and
            # "all three in the engine at once" never comes
            async with Worker(
                [agent], mesh=worker_mesh, owns_transport=True, max_workers=512
            ):
                ttl = 1.0
                client = Client.connect(client_mesh, lease_ttl=ttl)
                for i in range(3):
                    await client.agent("leased").send(f"orphan soak {i}")

                def submitted() -> int:
                    wave = (
                        len(engine._inflight["wave"])
                        if engine._inflight is not None else 0
                    )
                    return (
                        len(engine._active) + len(engine._pending)
                        + len(engine._carry) + len(engine._admitting)
                        + wave
                    )

                # ALL three sends must reach the engine before the
                # caller dies: a slow broker delivery arriving after the
                # reap would otherwise be counted (or not) by race
                await settle(
                    lambda: submitted() == 3,
                    message="the sends never all reached the engine",
                    ticks=3000, interval=0.01,  # first-use XLA compiles
                )
                # hard caller death over the real wire
                assert client._lease_task is not None
                client._lease_task.cancel()
                clock.advance(ttl + 0.5)
                await settle(
                    lambda: (
                        not engine._active
                        and not engine._pending
                        and not engine._carry
                        and engine._pend is None
                        and engine._inflight is None
                        and not engine._admitting
                        and len(engine._free) == runtime.max_batch_size
                        and engine._page_alloc.free_pages == total_free
                    ),
                    message="the engine never reaped the orphans",
                    **SETTLE,
                )
                assert_engine_drained(engine, total_free)
                assert engine.stats.orphaned_requests == 3
                await client.close()
            await engine.stop()
            await client_mesh.stop()
