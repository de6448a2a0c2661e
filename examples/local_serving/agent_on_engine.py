"""An agent served by the LOCAL inference engine — the TPU-native path.

Every other example uses deterministic scripted models so CI needs no
weights; this one runs the REAL serving stack end to end on the debug
preset (random weights, byte tokenizer): client -> mesh -> agent ->
JaxLocalModelClient -> continuous-batching engine with paged KV and
automatic prefix caching.  The second turn's prompt re-sends the same
instructions + history, so its prefill reuses the first turn's KV pages
— watch ``prefix_reused_tokens`` climb.

On real hardware, swap ``preset("debug")`` for
``JaxLocalModelClient(checkpoint="/path/to/llama-hf-dir",
runtime=RuntimeConfig(tp=8, quantization="int8", ...))``.

Run:
    python examples/local_serving/agent_on_engine.py
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

# pin only the DEFAULT: an explicit JAX_PLATFORMS (e.g. tpu on real
# hardware) wins
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from calfkit_tpu import Agent, Client, InMemoryMesh, Worker  # noqa: E402
from calfkit_tpu.inference.client import JaxLocalModelClient  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402


async def main() -> None:
    model = JaxLocalModelClient(
        config=preset("debug", max_seq_len=512),
        runtime=RuntimeConfig(
            max_batch_size=2,
            max_seq_len=512,
            prefill_chunk=16,
            decode_steps_per_dispatch=4,
            kv_layout="paged",
            page_size=16,
            num_kv_pages=160,
            chunked_prefill=True,
            prefix_cache=True,
        ),
        max_new_tokens=8,
    )
    agent = Agent(
        name="local",
        model=model,
        instructions=(
            "You are served by the local TPU-native engine. This "
            "instruction block spans several KV pages so the second "
            "turn's prefix reuse is visible in the stats."
        ),
    )
    mesh = InMemoryMesh()
    async with Worker([agent], mesh=mesh):
        client = Client.connect(mesh)
        await model.start()
        engine = model._engine
        for turn in (1, 2):
            result = await client.agent("local").execute(
                "say anything", timeout=120
            )
            print(
                f"turn {turn}: output={len(str(result.output))} chars, "
                f"reused so far="
                f"{engine.stats.prefix_reused_tokens} tokens"
            )
        assert engine.stats.prefix_reused_tokens > 0
        print(
            f"LOCAL ENGINE SERVED 2 turns; prefix cache reused "
            f"{engine.stats.prefix_reused_tokens} prompt tokens on turn 2"
        )
        await client.close()


if __name__ == "__main__":
    asyncio.run(main())
