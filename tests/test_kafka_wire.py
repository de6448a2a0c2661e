"""Native Kafka wire client + kafkad broker (VERDICT r3 item 4: the
"real broker" lane, in-image).  The transport-contract suite runs
KafkaWireMesh through the shared semantics tests; this file covers the
wire layer itself — codec vectors, RecordBatch round trips, the range
assignor — and the group-coordination behaviors a contract test can't
see (rebalance splits, takeover, commit-resume), plus a full agent
round trip over the wire mesh.

Reference anchor: tests/integration/ + Makefile test-kafka (the
reference's Redpanda lane); here the broker is the in-repo
``native/bin/kafkad`` speaking the same wire format.
"""

import asyncio

import pytest

from calfkit_tpu.mesh.kafka_wire import (
    KafkaWireClient,
    KafkaWireMesh,
    crc32c,
    decode_record_batches,
    encode_record_batch,
    find_kafkad,
    murmur2,
    partition_for,
    range_assign,
    spawn_kafkad,
)

pytestmark = pytest.mark.skipif(
    find_kafkad() is None, reason="kafkad not built (make -C native)"
)


@pytest.fixture(scope="module")
def broker_port():
    proc = spawn_kafkad(0)
    yield proc.kafkad_port
    proc.terminate()
    proc.wait(timeout=5)


class TestWireCodec:
    def test_crc32c_vector(self):
        # the canonical CRC-32C check value
        assert crc32c(b"123456789") == 0xE3069283

    def test_murmur2_vectors(self):
        # librdkafka rdmurmur2.c unittest vectors (Java-compatible)
        assert murmur2(b"kafka") == 0xD067CF64
        assert murmur2(b"") == 0x106E08D9
        assert murmur2(b"1234") == 0x9FC97B14
        assert murmur2(b"giberish123456789") == 0x8F552B0C

    def test_keyed_partitioning_is_deterministic(self):
        counter = [0]
        a = partition_for(b"run-42", 16, counter)
        b = partition_for(b"run-42", 16, counter)
        assert a == b
        # keyless round-robins
        seen = {partition_for(None, 4, counter) for _ in range(8)}
        assert seen == {0, 1, 2, 3}

    def test_record_batch_round_trip(self):
        records = [
            (b"k1", b"v1", [("trace", b"t1"), ("hop", b"2")]),
            (None, b"keyless", []),
            (b"tomb", None, []),  # null value = tombstone
        ]
        blob = encode_record_batch(records, 1_700_000_000_000)
        out = decode_record_batches(blob)
        assert [(k, v, h) for _o, _t, k, v, h in out] == records
        assert [o for o, *_ in out] == [0, 1, 2]

    def test_trace_headers_round_trip_through_record_batch(self):
        """ISSUE 2 satellite: the trace headers the observability layer
        rides on survive encode/decode — wire header values come back as
        BYTES and normalize through protocol.header_map, with missing
        headers tolerated (a None decode, never a KeyError)."""
        from calfkit_tpu import protocol
        from calfkit_tpu.observability.trace import TraceContext

        ctx = TraceContext(trace_id="corr-42", span_id="span-7")
        wire_headers = [
            (name, value.encode("utf-8"))
            for name, value in (
                ctx.headers() | {protocol.HDR_CORRELATION: "corr-42"}
            ).items()
        ]
        blob = encode_record_batch([(b"k", b"v", wire_headers)], 1234)
        [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
        # bytes-vs-str: raw wire values are bytes; header_map normalizes
        assert all(isinstance(v, bytes) for _n, v in decoded)
        normalized = protocol.header_map(dict(decoded))
        back = TraceContext.from_headers(normalized)
        assert back is not None
        assert back.trace_id == "corr-42"
        assert back.span_id == "span-7"
        assert normalized[protocol.HDR_CORRELATION] == "corr-42"

    def test_missing_and_undecodable_trace_headers_tolerated(self):
        from calfkit_tpu import protocol
        from calfkit_tpu.observability.trace import TraceContext

        # no headers at all survives the round trip as an untraced record
        blob = encode_record_batch([(b"k", b"v", [])], 1)
        [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
        assert TraceContext.from_headers(protocol.header_map(dict(decoded))) is None
        # an undecodable trace header value is DROPPED by header_map, so
        # the record degrades to untraced instead of crashing the consumer
        blob = encode_record_batch(
            [(b"k", b"v", [(protocol.HDR_TRACE, b"\xff\xfe\xfd")])], 1
        )
        [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
        normalized = protocol.header_map(dict(decoded))
        assert protocol.HDR_TRACE not in normalized
        assert TraceContext.from_headers(normalized) is None

    def test_run_header_round_trip_through_record_batch(self):
        """ISSUE 17 satellite: the ``x-mesh-run`` header (run identity
        carried verbatim across retries/failover/hedges) survives
        encode/decode and parses back to the exact (run_id, attempt)."""
        from calfkit_tpu import protocol

        value = protocol.format_run("a1b2c3d4e5f60718", 3)
        blob = encode_record_batch(
            [(b"k", b"v", [(protocol.HDR_RUN, value.encode("utf-8"))])], 99
        )
        [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
        normalized = protocol.header_map(dict(decoded))
        assert protocol.parse_run(normalized.get(protocol.HDR_RUN)) == (
            "a1b2c3d4e5f60718",
            3,
        )

    def test_corrupt_run_header_degrades_to_unlinked(self):
        """A corrupt ``x-mesh-run`` value degrades to an UN-LINKED run
        (parse_run → None) — never a shared bogus run id, never a
        delivery fault (the PR 5 corrupt-header law)."""
        from calfkit_tpu import protocol

        for raw in (
            b"\xff\xfe\xfd",  # undecodable utf-8
            b"no-separator",
            b"run:1.5",  # float is not an attempt counter
            b"run:nan",
            b"run:-1",
            b":7",  # empty run id
            b"",
        ):
            blob = encode_record_batch(
                [(b"k", b"v", [(protocol.HDR_RUN, raw)])], 1
            )
            [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
            normalized = protocol.header_map(dict(decoded))
            assert (
                protocol.parse_run(normalized.get(protocol.HDR_RUN)) is None
            )

    def test_priority_header_round_trip_through_record_batch(self):
        """ISSUE 20 satellite: the ``x-mesh-priority`` class header
        survives encode/decode and parses back to the exact class, for
        every class in the vocabulary."""
        from calfkit_tpu import protocol

        for cls in protocol.PRIORITY_CLASSES:
            value = protocol.format_priority(cls)
            blob = encode_record_batch(
                [(b"k", b"v", [(protocol.HDR_PRIORITY, value.encode("utf-8"))])],
                42,
            )
            [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
            normalized = protocol.header_map(dict(decoded))
            assert (
                protocol.parse_priority(normalized.get(protocol.HDR_PRIORITY))
                == cls
            )

    def test_corrupt_priority_header_degrades_to_default(self):
        """A corrupt ``x-mesh-priority`` value parses to None — the
        receiver resolves it to the DEFAULT class (qos.resolve_priority)
        — never a delivery fault, never a third class, and never a
        demotion below the default (the PR 5 corrupt-header law)."""
        from calfkit_tpu import protocol, qos

        for raw in (
            b"\xff\xfe\xfd",  # undecodable utf-8
            b"urgent",  # out-of-vocabulary
            b"INTERACTIVE",  # case matters: the vocabulary is exact
            b"batch ",  # trailing junk
            b"",
        ):
            blob = encode_record_batch(
                [(b"k", b"v", [(protocol.HDR_PRIORITY, raw)])], 1
            )
            [(_o, _t, _k, _v, decoded)] = decode_record_batches(blob)
            normalized = protocol.header_map(dict(decoded))
            parsed = protocol.parse_priority(
                normalized.get(protocol.HDR_PRIORITY)
            )
            assert parsed is None
            assert qos.resolve_priority(parsed) == protocol.DEFAULT_PRIORITY

    def test_range_assign_splits_evenly(self):
        members = {"m-1": ["a"], "m-2": ["a"]}
        partitions = {"a": [0, 1, 2, 3, 4]}
        out = range_assign(members, partitions)
        assert out["m-1"]["a"] == [0, 1, 2]
        assert out["m-2"]["a"] == [3, 4]
        # a member not subscribed to a topic gets none of it
        members = {"m-1": ["a"], "m-2": ["b"]}
        partitions = {"a": [0, 1], "b": [0]}
        out = range_assign(members, partitions)
        assert out["m-1"] == {"a": [0, 1]}
        assert out["m-2"] == {"b": [0]}


class TestWireBroker:
    async def test_produce_fetch_headers_tombstones(self, broker_port):
        client = KafkaWireClient("127.0.0.1", broker_port)
        await client.metadata(["t1"])
        base = await client.produce(
            "t1", 0,
            encode_record_batch(
                [(b"k", b"v", [("h", b"x")]), (b"k", None, [])], 1234,
            ),
        )
        assert base == 0
        fetched = await client.fetch([("t1", 0, 0)], max_wait_ms=100)
        [(_t, _p, err, blob)] = fetched
        assert err == 0
        recs = decode_record_batches(blob)
        assert recs[0][2:] == (b"k", b"v", [("h", b"x")])
        assert recs[1][3] is None  # tombstone survives the wire
        await client.close()

    async def test_fetch_from_middle_offset(self, broker_port):
        client = KafkaWireClient("127.0.0.1", broker_port)
        await client.metadata(["t2"])
        for i in range(5):
            await client.produce(
                "t2", 1,
                encode_record_batch([(None, b"m%d" % i, [])], 1000 + i),
            )
        fetched = await client.fetch([("t2", 1, 3)], max_wait_ms=100)
        [(_t, _p, _e, blob)] = fetched
        assert [v for _o, _t2, _k, v, _h in decode_record_batches(blob)] == [
            b"m3", b"m4",
        ]
        await client.close()

    async def test_commit_resume_across_group_restarts(self, broker_port):
        """Offsets committed by one consumer generation are where the next
        one resumes — the crash/restart contract."""
        mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        await mesh.start()
        topic = "resume-topic"
        await mesh.ensure_topics([topic])
        first, second = [], []

        async def h1(rec):
            first.append(rec.value)

        async def h2(rec):
            second.append(rec.value)

        sub = await mesh.subscribe([topic], h1, group_id="resume-g")
        for i in range(4):
            await mesh.publish(topic, b"a%d" % i, key=b"same-key")
        for _ in range(100):
            if len(first) == 4:
                break
            await asyncio.sleep(0.05)
        assert len(first) == 4
        await sub.stop()  # final commit on stop
        # records published while nobody is subscribed
        for i in range(3):
            await mesh.publish(topic, b"b%d" % i, key=b"same-key")
        sub2 = await mesh.subscribe([topic], h2, group_id="resume-g")
        for _ in range(200):
            if len(second) == 3:
                break
            await asyncio.sleep(0.05)
        # ONLY the gap records: committed offsets were honored
        assert second == [b"b0", b"b1", b"b2"]
        await sub2.stop()
        await mesh.stop()

    async def test_rebalance_splits_and_takeover(self, broker_port):
        mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        await mesh.start()
        topic = "split-topic"
        await mesh.ensure_topics([topic])
        got_a, got_b = [], []

        async def ha(rec):
            got_a.append(rec.value)

        async def hb(rec):
            got_b.append(rec.value)

        sub_a = await mesh.subscribe([topic], ha, group_id="split-g")
        sub_b = await mesh.subscribe([topic], hb, group_id="split-g")
        await asyncio.sleep(1.0)  # both generations settle
        # keys spread over all 8 partitions: both members must see work
        for i in range(24):
            await mesh.publish(topic, b"w%d" % i, key=b"key-%d" % i)
        for _ in range(200):
            if len(got_a) + len(got_b) == 24:
                break
            await asyncio.sleep(0.05)
        assert len(got_a) + len(got_b) == 24
        assert got_a and got_b, "range assignment must split the partitions"
        # one member leaves; the survivor owns everything
        await sub_b.stop()
        await asyncio.sleep(1.0)
        mark = len(got_a)
        for i in range(6):
            await mesh.publish(topic, b"z%d" % i, key=b"key-%d" % i)
        for _ in range(200):
            if len(got_a) - mark == 6:
                break
            await asyncio.sleep(0.05)
        assert len(got_a) - mark == 6
        await sub_a.stop()
        await mesh.stop()

    async def test_agent_round_trip_over_wire_mesh(self, broker_port):
        """The whole product path — client → kafkad (real Kafka wire
        protocol) → worker → agent → reply — with zero aiokafka."""
        from calfkit_tpu.client import Client
        from calfkit_tpu.engine import TestModelClient
        from calfkit_tpu.nodes import Agent
        from calfkit_tpu.worker import Worker

        mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        await client_mesh.start()
        agent = Agent(
            "wire_agent", model=TestModelClient(custom_output_text="over-kafka")
        )
        async with Worker([agent], mesh=mesh, owns_transport=True):
            client = Client.connect(client_mesh)
            result = await client.agent("wire_agent").execute("go", timeout=60)
            assert result.output == "over-kafka"
            await client.close()
        await client_mesh.stop()


class _Gate:
    """Holds the producer connection's requests until opened, so that a
    test decides what is pending while one is in flight."""

    def __init__(self, mesh: KafkaWireMesh):
        self._conn = mesh._producer.conn
        self._request = self._conn.request
        self._open = asyncio.Event()
        self._conn.request = self._held

    async def _held(self, api_key, version, body):
        await self._open.wait()
        return await self._request(api_key, version, body)

    def open(self) -> None:
        self._open.set()
        self._conn.request = self._request


async def _publish_behind_one_in_flight(mesh, topic: str, values) -> list:
    """Tasks publishing ``values``: the first's request is in flight (held
    by a closed gate) when the others are published, so they wait."""
    tasks = []
    for value in values:
        tasks.append(asyncio.ensure_future(mesh.publish(topic, value, key=b"k")))
        if len(tasks) == 1:
            await asyncio.sleep(0.01)
    await asyncio.sleep(0.01)
    return tasks


async def _values(port: int, topic: str, part: int = 0) -> list:
    client = KafkaWireClient("127.0.0.1", port)
    [(_t, _p, err, blob)] = await client.fetch([(topic, part, 0)], max_wait_ms=50)
    await client.close()
    assert err == 0
    return [v for _o, _ts, _k, v, _h in decode_record_batches(blob)]


async def _grouping_mesh(port: int, topic: str, **kwargs) -> KafkaWireMesh:
    """A started mesh whose first record is in ``topic`` (its partition
    count learned), with one partition so that order is one log's."""
    mesh = KafkaWireMesh(f"127.0.0.1:{port}", default_partitions=1, **kwargs)
    await mesh.start()
    await mesh.ensure_topics([topic])
    await mesh.publish(topic, b"first", key=b"k")
    return mesh


class TestProducerGroups:
    """What is published while a Produce request is in flight rides the
    next one together (ISSUE 41): order, acks, limits, cancellation."""

    async def test_a_lone_publish_is_one_request_of_one_record(self, broker_port):
        from calfkit_tpu.observability.metrics import metrics_text

        def served(name: str) -> int:  # what /metrics serves, process-wide
            [line] = [
                line for line in metrics_text().splitlines()
                if line.startswith(f"calfkit_mesh_produce_{name}_total ")
            ]
            return int(line.split()[1])

        before = served("requests"), served("records")
        mesh = await _grouping_mesh(broker_port, "grp-lone")
        assert (mesh.produce_requests, mesh.produce_records) == (1, 1)
        await mesh.publish("grp-lone", b"second", key=b"k")
        assert (mesh.produce_requests, mesh.produce_records) == (2, 2)
        assert (served("requests"), served("records")) == (
            before[0] + 2, before[1] + 2,
        )
        await mesh.stop()

    @pytest.mark.parametrize("n", [2, 128])
    async def test_concurrent_publishes_share_a_request_in_call_order(
        self, broker_port, n
    ):
        topic = f"grp-order-{n}"
        mesh = await _grouping_mesh(broker_port, topic)
        acked = []

        async def publish(i: int) -> None:
            await mesh.publish(topic, b"v%d" % i, key=b"k")
            # the ack came first: the broker's log already holds the record
            acked.append(i)

        await asyncio.gather(*(publish(i) for i in range(n)))
        assert mesh.produce_records == 1 + n
        assert mesh.produce_requests <= 3  # ONE or two carried all n
        assert sorted(acked) == list(range(n))
        assert await _values(broker_port, topic) == (
            [b"first"] + [b"v%d" % i for i in range(n)]
        )
        await mesh.stop()

    async def test_each_partition_of_a_shared_request_gets_its_own_result(
        self, broker_port
    ):
        """Several topics and partitions in ONE request; the partition the
        broker refuses fails its own producers and nobody else's."""
        from calfkit_tpu.mesh.kafka_wire import KafkaWireError

        client = KafkaWireClient("127.0.0.1", broker_port)
        await client.create_topics(["grp-a", "grp-b"], 2)
        await client.metadata(["grp-a", "grp-b"])
        wants = [("grp-a", 0), ("grp-a", 1), ("grp-b", 1), ("grp-b", 7),
                 ("grp-a", 0), ("grp-b", 7)]
        got = await asyncio.gather(*(
            client.produce_record(topic, part, (None, b"r%d" % i, []), 2, 1000)
            for i, (topic, part) in enumerate(wants)
        ), return_exceptions=True)
        assert (client.produce_requests, client.produce_records) == (1, 6)
        assert got[:3] == [0, 0, 0] and got[4] == 1  # offsets: a/0 took two
        for refused in (got[3], got[5]):  # kafkad: no partition 7
            assert isinstance(refused, KafkaWireError) and refused.code == 3
        assert await _values(broker_port, "grp-a", 0) == [b"r0", b"r4"]
        assert await _values(broker_port, "grp-b", 1) == [b"r2"]
        await client.close()

    async def test_a_cancelled_publisher_cancels_its_own_wait_alone(
        self, broker_port
    ):
        mesh = await _grouping_mesh(broker_port, "grp-cancel")
        writer = mesh._producer.conn._writer
        gate = _Gate(mesh)
        tasks = await _publish_behind_one_in_flight(
            mesh, "grp-cancel", (b"in-flight", b"queued", b"c", b"d")
        )
        tasks[0].cancel()  # its record is on the wire: it still lands
        tasks[1].cancel()  # its record never leaves
        gate.open()
        await asyncio.gather(*tasks[2:])
        assert tasks[0].cancelled() and tasks[1].cancelled()
        await mesh.publish("grp-cancel", b"after", key=b"k")
        # the request in flight was the sender's: nobody dropped the connection
        assert mesh._producer.conn._writer is writer
        assert await _values(broker_port, "grp-cancel") == [
            b"first", b"in-flight", b"c", b"d", b"after",
        ]
        await mesh.stop()

    async def test_a_partitions_batch_stops_at_max_message_bytes(
        self, broker_port
    ):
        mesh = await _grouping_mesh(
            broker_port, "grp-room", max_message_bytes=1000
        )
        gate = _Gate(mesh)
        values = [bytes([65 + i]) * 400 for i in range(6)]
        tasks = await _publish_behind_one_in_flight(mesh, "grp-room", values)
        gate.open()
        await asyncio.gather(*tasks)
        # one alone in flight, then 2 + 2 + 1: 401 bytes each, 1,000 a batch
        assert (mesh.produce_requests, mesh.produce_records) == (1 + 4, 1 + 6)
        assert await _values(broker_port, "grp-room") == [b"first"] + values
        with pytest.raises(ValueError):  # the per-record limit stands
            await mesh.publish("grp-room", b"x" * 1001, key=b"k")
        await mesh.stop()

    async def test_a_request_stops_at_its_bytes_and_keeps_a_partitions_order(self):
        """kafkad drops a frame over 64 MiB: a request carries at most half
        of that, and what it leaves waits IN ORDER behind what it left."""
        from collections import deque

        from calfkit_tpu.mesh.kafka_wire import _Pending

        mib = 1024 * 1024
        queue = deque(
            _Pending("t", part, (None, b"", []), size, 64 * mib)
            for part, size in ((0, 20 * mib), (1, 20 * mib), (1, 1), (2, 1))
        )
        second_of_1 = queue[2]
        taken = KafkaWireClient._take(queue)
        assert {tp: [e.size for e in es] for tp, es in taken.items()} == {
            ("t", 0): [20 * mib], ("t", 2): [1],
        }
        assert [e.size for e in queue] == [20 * mib, 1]
        assert queue[1] is second_of_1
        assert list(KafkaWireClient._take(queue)) == [("t", 1)] and not queue

    async def test_stop_fails_what_is_still_pending(self, broker_port):
        mesh = await _grouping_mesh(broker_port, "grp-stop")
        _Gate(mesh)  # never opened
        tasks = await _publish_behind_one_in_flight(
            mesh, "grp-stop", (b"in-flight", b"queued")
        )
        await mesh.stop()
        for outcome in await asyncio.gather(*tasks, return_exceptions=True):
            assert isinstance(outcome, RuntimeError)
        assert await _values(broker_port, "grp-stop") == [b"first"]
        with pytest.raises(RuntimeError):
            await mesh.publish("grp-stop", b"late", key=b"k")


class TestConfig4MultiAgent:
    """BASELINE config 4 over the REAL wire broker: 3 Agent nodes on
    shared topics with parallel tool calls, driven concurrently
    (reference analog: tests/test_concurrent_tool_calls.py — there over
    Redpanda, here over kafkad)."""

    async def test_three_agents_parallel_tools_concurrent_runs(self, broker_port):
        from calfkit_tpu.client import Client
        from calfkit_tpu.engine import FunctionModelClient
        from calfkit_tpu.models import ModelResponse
        from calfkit_tpu.models.messages import TextOutput, ToolCallOutput
        from calfkit_tpu.nodes import Agent, agent_tool
        from calfkit_tpu.worker import Worker

        @agent_tool
        def city_temp(city: str) -> float:
            """Temperature lookup.

            Args:
                city: City name.
            """
            return {"sf": 18.0, "nyc": 25.0}.get(city.lower(), 20.0)

        def scripted(messages, params):
            # first turn: TWO parallel tool calls; second: final answer
            has_returns = any(
                getattr(part, "kind", "") == "tool_return"
                for m in messages for part in getattr(m, "parts", [])
            )
            if not has_returns:
                return ModelResponse(parts=[
                    ToolCallOutput(tool_call_id="a", tool_name="city_temp",
                                   args={"city": "SF"}),
                    ToolCallOutput(tool_call_id="b", tool_name="city_temp",
                                   args={"city": "NYC"}),
                ])
            return ModelResponse(parts=[TextOutput(text="SF 18, NYC 25")])

        agents = [
            Agent(f"cfg4_agent_{i}", model=FunctionModelClient(scripted),
                  tools=[city_temp])
            for i in range(3)
        ]
        mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        client_mesh = KafkaWireMesh(f"127.0.0.1:{broker_port}")
        await client_mesh.start()
        async with Worker(
            [*agents, city_temp], mesh=mesh, owns_transport=True
        ):
            client = Client.connect(client_mesh)
            results = await asyncio.gather(*[
                client.agent(f"cfg4_agent_{i % 3}").execute(
                    f"temps {i}?", timeout=120
                )
                for i in range(6)
            ])
            assert [r.output for r in results] == ["SF 18, NYC 25"] * 6
            await client.close()
        await client_mesh.stop()
