"""Kernel corners from the reference's long-tail families: co-tenant tool
isolation, node-name validation, task-identity forwarding, node-side decode
floor (reference analogs: tests/test_co_tenant_tool_isolation.py,
test_node_id_validation.py, test_task_header_forwarding.py,
test_decode_floor.py); and the corners of the paged decode ATTENTION kernel
(PR 25): row lengths around a page edge, rows that read nothing, shared
tables, and the proof that a dead page is never read."""

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


# --------------------------------------------------------------------------- #
# the paged decode attention kernel (pallas_attention._paged_decode_kernel),
# interpret mode, at the head shapes of Mistral (K 8, G 4, hd 128), granite
# (K 8, G 4, hd 64) and TinyLlama (K 4, G 8, hd 64); page 64.  Heads of 64
# are read two positions a lane row (pallas_attention.lane_dense_pool).
# --------------------------------------------------------------------------- #

PD_WIDTHS = {"mistral": (8, 4, 128), "granite": (8, 4, 64), "tinyllama": (4, 8, 64)}
PD_PAGE, PD_WPAGES, PD_PMAX = 64, 4, 6
PD_WINDOW = PD_WPAGES * PD_PAGE

# name -> (row lengths, rows that are inactive, (row, row) sharing a table)
PAGED_DECODE_CASES = {
    "len-0": ([0], (), None),
    "len-1": ([1], (), None),
    "page-minus-1": ([PD_PAGE - 1], (), None),
    "page": ([PD_PAGE], (), None),
    "page-plus-1": ([PD_PAGE + 1], (), None),
    "odd": ([33, 191], (), None),
    "full-window": ([PD_WINDOW], (), None),
    "mixed": ([0, 1, PD_PAGE - 1, PD_PAGE, PD_PAGE + 1, PD_WINDOW, 130, 17], (), None),
    "inactive-row-on-trash-page": ([70, 100, 9], (1,), None),
    "shared-table": ([150, 150, 40], (), (0, 1)),
}


def _paged_decode_case(name: str, dtype, seed: int = 0, widths: str = "mistral"):
    """(q, pool_k, pool_v, tables, lens, live) for one named case: every
    row's pages are its own (page 0 is the trash page), an inactive row's
    table is all trash and its length 0 as ``decode_step_ring_paged``
    hands it down, a shared table is one row's copied to the other."""
    import jax.numpy as jnp
    import numpy as np

    K, G, hd = PD_WIDTHS[widths]
    lens, inactive, shared = PAGED_DECODE_CASES[name]
    lens = list(lens)
    B = len(lens)
    rng = np.random.default_rng(seed)
    n_pages = 1 + sum(-(-n // PD_PAGE) for n in lens) + 3  # + unused pages
    tables = np.zeros((B, PD_PMAX), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        if b in inactive:
            lens[b] = 0
            continue
        for p in range(-(-n // PD_PAGE)):
            tables[b, p] = nxt
            nxt += 1
    if shared is not None:
        tables[shared[1]] = tables[shared[0]]
    live = np.zeros((n_pages,), bool)
    for b, n in enumerate(lens):
        live[tables[b, : -(-n // PD_PAGE)]] = True
    shape = (2, n_pages, K, PD_PAGE, hd)
    pool_k = rng.standard_normal(shape).astype(np.float32)
    pool_v = rng.standard_normal(shape).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), dtype)
    return (
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
        live,
    )


def _paged_decode_both(q, pool_k, pool_v, tables, lens, *, pages_per_block):
    """The kernel (layer 1 of the whole pool) beside the XLA law it
    replaces: ``masked_attention_source`` over ``gather_window_paged``."""
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference.pallas_attention import (
        paged_decode_attention_pallas,
    )

    got = paged_decode_attention_pallas(
        q, pool_k, pool_v, jnp.int32(1), tables, lens, wpages=PD_WPAGES,
        interpret=True, pages_per_block=pages_per_block,
    )
    valid = jnp.arange(PD_WINDOW)[None, :] < lens[:, None]
    o, m, z = M.masked_attention_source(
        q, M.gather_window_paged(pool_k[1], tables, PD_WPAGES),
        M.gather_window_paged(pool_v[1], tables, PD_WPAGES), valid,
    )
    return got, (o, m[..., 0], z[..., 0])


class TestPagedDecodeKernelCorners:
    @pytest.mark.parametrize("pages_per_block", [1, 2])
    @pytest.mark.parametrize("case", sorted(PAGED_DECODE_CASES))
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_matches_gathered_window(self, widths, case, pages_per_block):
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            case, jnp.float32, widths=widths
        )
        got, want = _paged_decode_both(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), tables, lens,
            pages_per_block=pages_per_block,
        )
        for name, g, w in zip("omz", got, want):
            # one pass over the window against a block at a time: the
            # same sums in another order
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
                err_msg=f"{case}: {name} diverged",
            )
        # a row that reads nothing stays finite at the floor
        empty = np.asarray(lens) == 0
        assert (np.asarray(got[1])[empty] == np.float32(-1e29)).all()
        assert (np.asarray(got[2])[empty] == 0).all()

    @pytest.mark.parametrize("case", ["mixed", "shared-table"])
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_bf16_operands_f32_accumulation(self, widths, case):
        """The configuration's precision: bf16 q, K, V into the products,
        float32 scores, statistics and accumulator."""
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            case, jnp.bfloat16, widths=widths
        )
        got, want = _paged_decode_both(
            q, jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16), tables, lens,
            pages_per_block=2,
        )
        assert all(a.dtype == jnp.float32 for a in got)
        norm = lambda o, m, z: np.asarray(o / jnp.maximum(z[..., None], 1e-30))
        # p is rounded to bf16 against a running maximum here and against
        # the row's own there: agreement to bf16's 8 bits, not float32's
        np.testing.assert_allclose(norm(*got), norm(*want), atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(got[1]), np.asarray(want[1]), rtol=1e-5
        )

    @pytest.mark.parametrize("pages_per_block", [1, 2, 4])
    @pytest.mark.parametrize(
        "case", ["mixed", "inactive-row-on-trash-page", "shared-table"]
    )
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_dead_pages_are_never_read(self, widths, case, pages_per_block):
        """Every page no row's length reaches — the trash page, the tail of
        each table, the unused pages of the pool, both layers' — is NaN;
        the result is finite and equal to the clean pool's.  The XLA
        gather reads them all (and masks them), so it gets the clean pool."""
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, live = _paged_decode_case(
            case, jnp.float32, seed=5, widths=widths
        )
        dirty_k, dirty_v = pool_k.copy(), pool_v.copy()
        dirty_k[:, ~live] = np.nan
        dirty_v[:, ~live] = np.nan
        dirty_k[0] = dirty_v[0] = np.nan  # another layer's pages
        got, _ = _paged_decode_both(
            q, jnp.asarray(dirty_k), jnp.asarray(dirty_v), tables, lens,
            pages_per_block=pages_per_block,
        )
        clean, want = _paged_decode_both(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), tables, lens,
            pages_per_block=pages_per_block,
        )
        for g, c, w in zip(got, clean, want):
            assert np.isfinite(np.asarray(g)).all()
            np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5
            )

    @pytest.mark.parametrize(
        "head_dim,page,dtype,ok",
        [
            (128, 64, "bfloat16", True),
            (128, 16, "bfloat16", True),
            (256, 8, "float32", True),
            (64, 64, "bfloat16", True),  # two positions a lane row (TinyLlama)
            (64, 32, "bfloat16", True),  # ... on a packed page of 16 rows
            (32, 64, "bfloat16", True),  # four positions a lane row
            (64, 16, "float32", True),
            (128, 8, "bfloat16", False),  # half a packed sublane tile
            (128, 16, "int8", False),
            (64, 16, "bfloat16", False),  # a packed page under a sublane tile
            (64, 8, "float32", False),
            (96, 64, "bfloat16", False),  # a head that does not divide 128
            (80, 64, "float32", False),
            (192, 64, "bfloat16", False),  # nor is whole lane tiles
        ],
    )
    def test_shape_rule(self, head_dim, page, dtype, ok):
        from calfkit_tpu.inference.pallas_attention import (
            paged_decode_in_place_ok,
        )

        assert paged_decode_in_place_ok(head_dim, page, dtype) is ok

    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_a_view_made_by_the_caller_is_read_as_it_lies(self, widths):
        """The engine makes ``lane_dense_pool`` once a dispatch and hands it
        down: the kernel's result is bit for bit that of the pool as it
        lies.  For whole lane tiles, and outside the shape rule, the view
        IS the pool: the same array, no operation."""
        import jax.numpy as jnp
        import numpy as np

        from calfkit_tpu.inference import pallas_attention as PA

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            "mixed", jnp.bfloat16, widths=widths
        )
        pool_k = jnp.asarray(pool_k, jnp.bfloat16)
        pool_v = jnp.asarray(pool_v, jnp.bfloat16)
        view_k, view_v = PA.lane_dense_pool(pool_k), PA.lane_dense_pool(pool_v)
        hd = pool_k.shape[-1]
        if hd % 128 == 0:
            assert view_k is pool_k and view_v is pool_v
        else:
            f = PA.paged_decode_lane_pack(hd)
            assert view_k.shape == (*pool_k.shape[:3], PD_PAGE // f, 128)
            # row r of a page: positions f * r .. f * r + f - 1 side by side
            np.testing.assert_array_equal(
                np.asarray(view_k[1, 2, 3, 5], np.float32),
                np.asarray(pool_k[1, 2, 3, 5 * f:(5 + 1) * f], np.float32).ravel(),
            )
        kw = dict(wpages=PD_WPAGES, interpret=True)
        got = PA.paged_decode_attention_pallas(
            q, view_k, view_v, jnp.int32(1), tables, lens, **kw)
        want = PA.paged_decode_attention_pallas(
            q, pool_k, pool_v, jnp.int32(1), tables, lens, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        small = jnp.zeros((1, 3, 2, 16, 64), jnp.bfloat16)  # outside the rule
        assert PA.lane_dense_pool(small) is small

    def test_other_shapes_keep_the_ragged_row(self):
        """hd 64 on pages of 8 (a packed page of 4 rows, under a sublane
        tile) is outside the shape rule: explicit "pallas" still runs a
        kernel there, the S = 1 row of the ragged paged one."""
        import jax.numpy as jnp
        import numpy as np

        from calfkit_tpu.inference import pallas_attention as PA

        rng = np.random.default_rng(2)
        pool = jnp.asarray(rng.standard_normal((1, 5, 2, 8, 64)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((2, 2, 4, 64)), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
        lens = jnp.asarray([11, 8], jnp.int32)
        before = PA.KERNEL_TRACES["ragged_paged", "interpreted"]
        o, m, z = PA.paged_decode_attention_pallas(
            q, pool, pool, jnp.int32(0), tables, lens, wpages=2,
            interpret=True,
        )
        assert PA.KERNEL_TRACES["ragged_paged", "interpreted"] == before + 1
        assert o.shape == (2, 2, 4, 64) and m.shape == z.shape == (2, 2, 4)
        assert np.isfinite(np.asarray(o)).all()
