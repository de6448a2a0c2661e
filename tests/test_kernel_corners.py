"""Kernel corners from the reference's long-tail families: co-tenant tool
isolation, node-name validation, task-identity forwarding, node-side decode
floor (reference analogs: tests/test_co_tenant_tool_isolation.py,
test_node_id_validation.py, test_task_header_forwarding.py,
test_decode_floor.py)."""

import pytest

from calfkit_tpu import protocol
from calfkit_tpu.client import Client
from calfkit_tpu.engine import FunctionModelClient, TestModelClient
from calfkit_tpu.mesh import InMemoryMesh
from calfkit_tpu.models import ModelResponse, TextOutput, ToolCallOutput
from calfkit_tpu.nodes import Agent, agent_tool
from calfkit_tpu.worker import Worker


class TestCoTenantToolIsolation:
    async def test_two_agents_one_worker_distinct_tools(self):
        """Co-tenant agents must each see ONLY their own eager tools —
        sharing a worker process shares nothing else."""
        views: dict[str, list[str]] = {}

        @agent_tool
        def tool_a(x: int) -> int:
            """A.

            Args:
                x: X.
            """
            return x

        @agent_tool
        def tool_b(x: int) -> int:
            """B.

            Args:
                x: X.
            """
            return x

        def make_model(name):
            def model(messages, params):
                views[name] = sorted(t.name for t in params.tool_defs)
                return ModelResponse(parts=[TextOutput(text="ok")])
            return FunctionModelClient(model)

        alpha = Agent("iso_a", model=make_model("iso_a"), tools=[tool_a])
        beta = Agent("iso_b", model=make_model("iso_b"), tools=[tool_b])
        mesh = InMemoryMesh()
        async with Worker([alpha, beta, tool_a, tool_b], mesh=mesh,
                          owns_transport=True):
            client = Client.connect(mesh)
            await client.agent("iso_a").execute("go", timeout=10)
            await client.agent("iso_b").execute("go", timeout=10)
            await client.close()
        assert views["iso_a"] == ["tool_a"]
        assert views["iso_b"] == ["tool_b"]

    async def test_concurrent_runs_do_not_cross_state(self):
        """Two interleaved runs on one agent: each model turn sees its own
        run's prompt only (single-writer per task, state rides the wire)."""
        import asyncio

        def model(messages, params):
            from calfkit_tpu.models.messages import ModelRequest, UserPart

            texts = [
                str(p.content)
                for m in messages
                if isinstance(m, ModelRequest)
                for p in m.parts
                if isinstance(p, UserPart)
            ]
            return ModelResponse(parts=[TextOutput(text="|".join(texts))])

        agent = Agent("tenant", model=FunctionModelClient(model))
        mesh = InMemoryMesh()
        async with Worker([agent], mesh=mesh, owns_transport=True):
            client = Client.connect(mesh)
            gateway = client.agent("tenant")
            results = await asyncio.gather(
                *(gateway.execute(f"run-{i}", timeout=15) for i in range(6))
            )
            for i, result in enumerate(results):
                assert result.output == f"run-{i}"
            await client.close()


class TestNodeNaming:
    def test_agent_names_must_be_topic_safe(self):
        with pytest.raises(Exception):
            Agent("has space", model=TestModelClient())
        with pytest.raises(Exception):
            Agent("has/slash", model=TestModelClient())
        Agent("fine-name_1", model=TestModelClient())  # dots/dash/underscore ok

    def test_topic_grammar(self):
        assert protocol.is_topic_safe("agent.x.private.input")
        assert not protocol.is_topic_safe("")
        assert not protocol.is_topic_safe("a b")
        assert not protocol.is_topic_safe("x" * 300)  # kafka length cap


class TestTaskIdentityForwarding:
    async def test_one_task_id_spans_agent_and_tool_hops(self):
        """The client-minted task id is the partition key of EVERY hop."""
        seen: dict[str, set] = {"keys": set(), "tasks": set()}
        mesh = InMemoryMesh()

        @agent_tool
        def echo_tool(x: int) -> int:
            """E.

            Args:
                x: X.
            """
            return x

        def model(messages, params):
            from calfkit_tpu.models.messages import ModelRequest, ToolReturnPart

            done = any(
                isinstance(p, ToolReturnPart)
                for m in messages
                if isinstance(m, ModelRequest)
                for p in m.parts
            )
            if not done:
                return ModelResponse(parts=[ToolCallOutput(
                    tool_call_id="t1", tool_name="echo_tool", args={"x": 1})])
            return ModelResponse(parts=[TextOutput(text="done")])

        agent = Agent("spanner", model=FunctionModelClient(model),
                      tools=[echo_tool])

        async def tap(record):
            if record.key:
                seen["keys"].add(record.key)
            task = record.headers.get(protocol.HDR_TASK)
            if task:
                seen["tasks"].add(task)

        async with Worker([agent, echo_tool], mesh=mesh, owns_transport=True):
            sub = await mesh.subscribe(
                ["agent.spanner.private.input", "tool.echo_tool.input",
                 "agent.spanner.private.return"],
                tap, group_id=None, ordered=False,
            )
            client = Client.connect(mesh)
            result = await client.agent("spanner").execute("go", timeout=15)
            assert result.output == "done"
            assert result.task_id is not None
            await sub.stop()
            await client.close()
        assert seen["tasks"] == {result.task_id}
        assert len(seen["keys"]) == 1  # one partition key end-to-end


class TestNodeDecodeFloor:
    async def test_garbage_on_the_input_topic_does_not_wedge_the_agent(self):
        agent = Agent("sturdy", model=TestModelClient(custom_output_text="alive"))
        mesh = InMemoryMesh()
        async with Worker([agent], mesh=mesh, owns_transport=True):
            # hostile bytes with envelope-shaped headers
            await mesh.publish(
                "agent.sturdy.private.input",
                b"\xff\xfe not json at all",
                key=b"k1",
                headers={
                    protocol.HDR_KIND: "call",
                    protocol.HDR_WIRE: "envelope",
                    protocol.HDR_TASK: "t-garbage",
                },
            )
            client = Client.connect(mesh)
            result = await client.agent("sturdy").execute("still there?",
                                                          timeout=10)
            assert result.output == "alive"
            await client.close()
