"""Per-partition leader routing in the wire client (VERDICT r4 item 4).

kafkad is single-node, so the spread-leader paths are exercised here
against an in-test TWO-broker fake cluster speaking the wire format:
metadata names different leaders per partition, produce/fetch must land
on the right broker, NOT_LEADER answers must trigger refresh-and-retry,
and group APIs must ride the coordinator.
"""

from __future__ import annotations

import asyncio
import struct

import pytest

from calfkit_tpu.mesh.kafka_wire import (
    ERR_NOT_LEADER,
    KafkaWireClient,
    KafkaWireError,
    encode_record_batch,
)


class _FakeBroker:
    """Minimal wire-speaking broker: Metadata v1, Produce v3, Fetch v4,
    FindCoordinator v0, Heartbeat v1.  The CLUSTER decides who leads
    which partition; each broker answers produce/fetch only for the
    partitions it currently leads (NOT_LEADER otherwise) and records
    every produce it accepted."""

    def __init__(self, cluster: "_FakeCluster", node_id: int):
        self.cluster = cluster
        self.node_id = node_id
        self.port = 0
        self.produced: list[tuple[str, int, bytes]] = []
        self.heartbeats = 0
        self._server: asyncio.AbstractServer | None = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve, "127.0.0.1", 0
        )
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server:
            self._server.close()
            await self._server.wait_closed()

    async def _serve(self, reader, writer) -> None:
        try:
            while True:
                szbuf = await reader.readexactly(4)
                (size,) = struct.unpack(">i", szbuf)
                blob = await reader.readexactly(size)
                api, _ver, corr = struct.unpack(">hhi", blob[:8])
                (cid_len,) = struct.unpack(">h", blob[8:10])
                body = blob[10 + max(0, cid_len):]
                out = struct.pack(">i", corr) + self._handle(api, body)
                writer.write(struct.pack(">i", len(out)) + out)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    # ----------------------------------------------------------- encoding
    @staticmethod
    def _s(text: str) -> bytes:
        raw = text.encode()
        return struct.pack(">h", len(raw)) + raw

    def _handle(self, api: int, body: bytes) -> bytes:
        if api == 3:
            return self._metadata()
        if api == 0:
            return self._produce(body)
        if api == 1:
            return self._fetch(body)
        if api == 10:
            coord = self.cluster.coordinator
            return (struct.pack(">hi", 0, coord.node_id)
                    + self._s("127.0.0.1") + struct.pack(">i", coord.port))
        if api == 12:
            self.heartbeats += 1
            code = 0 if self is self.cluster.coordinator else 16
            return struct.pack(">ih", 0, code)
        raise AssertionError(f"fake broker got api {api}")

    def _metadata(self) -> bytes:
        out = struct.pack(">i", len(self.cluster.brokers))
        for broker in self.cluster.brokers:
            out += (struct.pack(">i", broker.node_id) + self._s("127.0.0.1")
                    + struct.pack(">i", broker.port) + struct.pack(">h", -1))
        out += struct.pack(">i", 0)  # controller
        topics: dict[str, dict[int, int]] = {}
        for (topic, part), node in self.cluster.leaders.items():
            topics.setdefault(topic, {})[part] = node
        out += struct.pack(">i", len(topics))
        for topic, parts in topics.items():
            out += struct.pack(">h", 0) + self._s(topic) + b"\x00"
            out += struct.pack(">i", len(parts))
            for part, node in parts.items():
                out += struct.pack(">hii", 0, part, node)
                out += struct.pack(">ii", 0, 0)  # replicas, isr
        return out

    def _produce(self, body: bytes) -> bytes:
        r_off = 0
        # skip transactional_id(-1 string), acks, timeout, topic count(=1)
        r_off += 2 + 2 + 4 + 4
        (tlen,) = struct.unpack_from(">h", body, r_off)
        r_off += 2
        topic = body[r_off:r_off + tlen].decode()
        r_off += tlen + 4  # partition count (=1)
        (part,) = struct.unpack_from(">i", body, r_off)
        r_off += 4
        (blen,) = struct.unpack_from(">i", body, r_off)
        r_off += 4
        batch = body[r_off:r_off + blen]
        if self.cluster.leaders.get((topic, part)) == self.node_id:
            self.produced.append((topic, part, batch))
            err, base = 0, len(self.produced) - 1
        else:
            err, base = ERR_NOT_LEADER, -1
        return (struct.pack(">i", 1) + self._s(topic) + struct.pack(">i", 1)
                + struct.pack(">ih", part, err)
                + struct.pack(">qq", base, -1))

    def _fetch(self, body: bytes) -> bytes:
        off = 4 + 4 + 4 + 4 + 1  # replica, max_wait, min_bytes, max_bytes, isolation
        (ntopics,) = struct.unpack_from(">i", body, off)
        off += 4
        wants: list[tuple[str, int]] = []
        for _ in range(ntopics):
            (tlen,) = struct.unpack_from(">h", body, off)
            off += 2
            topic = body[off:off + tlen].decode()
            off += tlen
            (nparts,) = struct.unpack_from(">i", body, off)
            off += 4
            for _ in range(nparts):
                (part,) = struct.unpack_from(">i", body, off)
                off += 4 + 8 + 4  # partition, offset, max_bytes
                wants.append((topic, part))
        out = struct.pack(">i", 0)  # throttle
        by_topic: dict[str, list[int]] = {}
        for topic, part in wants:
            by_topic.setdefault(topic, []).append(part)
        out += struct.pack(">i", len(by_topic))
        for topic, parts in by_topic.items():
            out += self._s(topic) + struct.pack(">i", len(parts))
            for part in parts:
                lead_here = self.cluster.leaders.get((topic, part)) == self.node_id
                err = 0 if lead_here else ERR_NOT_LEADER
                blob = b""
                if lead_here:
                    blob = b"".join(
                        batch for t, p, batch in self.produced
                        if t == topic and p == part
                    )
                out += struct.pack(">ih", part, err)
                out += struct.pack(">qq", 1, 1)  # hwm, last stable
                out += struct.pack(">i", 0)      # aborted
                out += struct.pack(">i", len(blob)) + blob
        return out


class _FakeCluster:
    def __init__(self):
        self.brokers = [_FakeBroker(self, 0), _FakeBroker(self, 1)]
        self.leaders: dict[tuple[str, int], int] = {}
        self.coordinator: _FakeBroker = self.brokers[1]

    async def __aenter__(self):
        for broker in self.brokers:
            await broker.start()
        return self

    async def __aexit__(self, *exc):
        for broker in self.brokers:
            await broker.stop()


class TestLeaderRouting:
    def test_produce_routes_to_each_partition_leader(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0, ("t", 1): 1}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.metadata(["t"])
                    batch = encode_record_batch([(b"k", b"v", [])], 1)
                    await client.produce("t", 0, batch)
                    await client.produce("t", 1, batch)
                    assert [p for _t, p, _b in cluster.brokers[0].produced] == [0]
                    assert [p for _t, p, _b in cluster.brokers[1].produced] == [1]
                finally:
                    await client.close()

        asyncio.run(run())

    def test_fetch_fans_out_to_leaders_and_merges(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0, ("t", 1): 1}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.metadata(["t"])
                    batch = encode_record_batch([(b"k", b"v", [])], 1)
                    await client.produce("t", 0, batch)
                    await client.produce("t", 1, batch)
                    results = await client.fetch([("t", 0, 0), ("t", 1, 0)])
                    got = {(t, p): (err, blob) for t, p, err, blob in results}
                    assert got[("t", 0)][0] == 0 and got[("t", 0)][1]
                    assert got[("t", 1)][0] == 0 and got[("t", 1)][1]
                finally:
                    await client.close()

        asyncio.run(run())

    def test_leader_move_triggers_refresh_and_retry(self):
        """Leadership moves AFTER the client cached it: the stale broker
        answers NOT_LEADER, the client must re-learn and succeed without
        surfacing an error."""

        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.metadata(["t"])
                    batch = encode_record_batch([(b"k", b"v", [])], 1)
                    await client.produce("t", 0, batch)
                    cluster.leaders[("t", 0)] = 1  # leadership moves
                    await client.produce("t", 0, batch)  # must NOT raise
                    assert len(cluster.brokers[1].produced) == 1
                finally:
                    await client.close()

        asyncio.run(run())

    def test_fetch_not_leader_refreshes_routing(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.metadata(["t"])
                    cluster.leaders[("t", 0)] = 1
                    first = await client.fetch([("t", 0, 0)])
                    assert first[0][2] == ERR_NOT_LEADER  # surfaced once...
                    second = await client.fetch([("t", 0, 0)])
                    assert second[0][2] == 0  # ...then routed correctly
                finally:
                    await client.close()

        asyncio.run(run())

    def test_a_record_is_sent_again_once_and_then_fails_its_publisher(self):
        """The grouped path (``produce_record``) keeps the once-only retry:
        a move is followed, a second NOT_LEADER is the publisher's error."""

        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                record = (b"k", b"v", [])
                try:
                    await client.metadata(["t"])
                    cluster.leaders[("t", 0)] = 1  # leadership moves
                    await client.produce_record("t", 0, record, 2, 1000)
                    assert len(cluster.brokers[1].produced) == 1
                    assert client.produce_requests == 2
                    cluster.leaders[("t", 0)] = 7  # to a broker nobody knows
                    with pytest.raises(KafkaWireError) as refused:
                        await client.produce_record("t", 0, record, 2, 1000)
                    assert refused.value.code == ERR_NOT_LEADER
                    assert client.produce_requests == 4  # twice, not more
                finally:
                    await client.close()

        asyncio.run(run())

    def test_unrouted_produce_refreshes_and_succeeds(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                # metadata deliberately NOT fetched; partition led by 1
                # but bootstrap is broker 0 and metadata refresh still
                # reports broker 1 → retry succeeds
                cluster.leaders = {("t", 0): 1}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    batch = encode_record_batch([(b"k", b"v", [])], 1)
                    await client.produce("t", 0, batch)
                    assert len(cluster.brokers[1].produced) == 1
                finally:
                    await client.close()

        asyncio.run(run())


class TestCoordinatorRouting:
    def test_group_apis_ride_the_coordinator(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.ensure_coordinator("g")
                    code = await client.heartbeat("g", 1, "m")
                    assert code == 0  # answered by the coordinator itself
                    assert cluster.brokers[1].heartbeats == 1
                    assert cluster.brokers[0].heartbeats == 0
                finally:
                    await client.close()

        asyncio.run(run())

    def test_not_coordinator_is_refreshable(self):
        async def run() -> None:
            async with _FakeCluster() as cluster:
                cluster.leaders = {("t", 0): 0}
                client = KafkaWireClient("127.0.0.1", cluster.brokers[0].port)
                try:
                    await client.ensure_coordinator("g")
                    cluster.coordinator = cluster.brokers[0]  # moves
                    code = await client.heartbeat("g", 1, "m")
                    assert code == 16  # NOT_COORDINATOR surfaced
                    client.forget_coordinator()
                    await client.ensure_coordinator("g")
                    assert await client.heartbeat("g", 1, "m") == 0
                finally:
                    await client.close()

        asyncio.run(run())
