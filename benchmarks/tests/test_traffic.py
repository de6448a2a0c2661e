"""The generator: deterministic in the seed, same work for every seed, the
stated means, and token counts that match the program's own rendering."""

import collections
import json
import os
import random

import pytest

from benchmarks import traffic as T

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_quantiles_hit_the_stated_laws():
    chat = mix("chat-open")
    prompts = T.quantiles(chat["prompt_tokens"], 4000)
    assert min(prompts) >= 32 and max(prompts) <= 1536
    assert abs(sorted(prompts)[2000] - 256) <= 2  # the median
    outs = T.quantiles(chat["output_tokens"], 4000)
    share = collections.Counter(outs)
    assert [round(share[v] / 4000, 2) for v in (32, 64, 128, 256)] == [0.3, 0.3, 0.25, 0.15]
    assert abs(sum(outs) / 4000 - 99.2) < 0.1  # 0.3x32 + 0.3x64 + 0.25x128 + 0.15x256
    users = T.quantiles({"law": "uniform", "min": 64, "max": 256}, 1000)
    assert abs(sum(users) / 1000 - 160) < 1


def test_arrivals_mean_rate_and_bursts():
    chat = mix("chat-open")
    times = T.arrival_times(chat["arrivals"], 10.0, 600.0)
    assert abs(len(times) / 600.0 - 10.0) < 0.5  # the stated mean rate
    assert times == sorted(times) and times[-1] < 600.0
    a = chat["arrivals"]
    in_burst = [t for t in times if (t - a["burst_offset_s"]) % a["burst_every_s"] < a["burst_len_s"]]
    # 2 s in 10 at three times the base rate: 6 of 14 parts
    assert abs(len(in_burst) / len(times) - 6 / 14) < 0.04
    assert times == T.arrival_times(chat["arrivals"], 10.0, 600.0)  # canonical


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_open_schedule_same_work_for_every_seed(seed):
    chat = mix("chat-open")
    base = T.Traffic(chat, {"rate_rps": 8.0}, 1).open_schedule(30.0)
    other = T.Traffic(chat, {"rate_rps": 8.0}, seed).open_schedule(30.0)
    again = T.Traffic(chat, {"rate_rps": 8.0}, seed).open_schedule(30.0)
    assert [(r.due_s, r.prompt, r.out_tokens) for r in other] == \
           [(r.due_s, r.prompt, r.out_tokens) for r in again]
    assert [r.due_s for r in base] == [r.due_s for r in other]  # same arrivals
    assert sorted(r.prompt_tokens for r in base) == sorted(r.prompt_tokens for r in other)
    assert sorted(r.out_tokens for r in base) == sorted(r.out_tokens for r in other)
    assert [r.prompt_tokens for r in base] == [r.prompt_tokens for r in other]  # same order
    if seed != 1:
        assert [r.prompt for r in base] != [r.prompt for r in other]  # the seed draws the text
    assert all(len(r.prompt.encode()) + T.rendered_tokens(0, [], 0) == r.prompt_tokens
               for r in other)


def test_rendered_tokens_equal_the_programs_rendering():
    from calfkit_tpu.engine.model_client import ModelRequestParameters
    from calfkit_tpu.inference.client import render_messages
    from calfkit_tpu.models.messages import ModelRequest, ModelResponse, TextOutput, UserPart

    rng = random.Random(3)
    system = T.text(rng, 100)
    turns = [(T.text(rng, 70), T.text(rng, 48)), (T.text(rng, 9), T.text(rng, 48))]
    user = T.text(rng, 33)
    messages = []
    for u, a in turns:
        messages += [ModelRequest(parts=[UserPart(content=u)]),
                     ModelResponse(parts=[TextOutput(text=a)])]
    messages.append(ModelRequest(parts=[UserPart(content=user)], instructions=system))
    rendered = render_messages(messages, ModelRequestParameters())
    want = T.rendered_tokens(100, [(70, 48), (9, 48)], 33)
    assert 1 + len(rendered.encode()) == want  # BOS + one token a byte
    plain = render_messages([ModelRequest(parts=[UserPart(content=user)])],
                            ModelRequestParameters())
    assert 1 + len(plain.encode()) == T.rendered_tokens(0, [], 33)


def test_sessions_share_prefixes_and_stay_inside_the_context():
    spec = mix("agent-sessions")
    tr = T.Traffic(spec, {"callers": 16}, 5)
    assert [a.name for a in tr.agents()] == ["sys0", "sys1", "sys2", "sys3"]
    assert all(len(a.instructions) == 2048 and a.max_tokens == 48 for a in tr.agents())
    stream = tr.caller_stream(3)
    reqs = [next(stream) for _ in range(20)]
    assert all(r.prompt_tokens + 48 + 1 <= spec["session"]["max_context_tokens"] for r in reqs)
    first = [r for r in reqs if not r.history]
    assert len(first) >= 2 and len(reqs[1].history) == 1
    # turn k+1 carries turn k's user text and a seeded answer of the budgeted length
    assert reqs[1].history[0][0] == reqs[0].prompt and len(reqs[1].history[0][1]) == 48
    same = T.Traffic(spec, {"callers": 16}, 5).caller_stream(3)
    assert [next(same).prompt for _ in range(20)] == [r.prompt for r in reqs]


def test_warm_sessions_visit_both_reuse_classes_of_every_bucket():
    tr = T.Traffic(mix("agent-sessions"), {"callers": 16}, 1)
    steps = list(tr.warm_sessions(512, 2, 1))
    lens = [s[0].prompt_tokens for s in steps]
    assert all(len(s) == 2 and s[0].prompt_tokens == s[1].prompt_tokens for s in steps)
    assert lens == sorted(lens)
    assert {-(-n // 512) * 512 for n in lens} == {2560, 3072, 3584, 4096}


def test_rehearsal_scale_divides_lengths():
    tr = T.Traffic(mix("chat-open"), {"rate_rps": 5.0}, 1, scale=8)
    assert tr.prompt_range() == (4, 192)
    assert [a.max_tokens for a in tr.agents()] == [4, 8, 16, 32]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_closed_loop_same_sizes_in_the_same_order(seed):
    spec = mix("batch-closed")
    def sizes(s):
        streams = [T.Traffic(spec, {"callers": 4}, s).caller_stream(c) for c in range(4)]
        return [(r.prompt_tokens, r.out_tokens) for st in streams for r in (next(st) for _ in range(1024))]
    base, other = sizes(1), sizes(seed)
    assert base == other  # the seed must not change the work: it draws the text only
    assert len({p for p, _ in base}) > 100 and {o for _, o in base} == {64, 128}


def test_ramp_blocks_are_the_same_process_and_deterministic():
    chat = mix("chat-open")
    tr = T.Traffic(chat, {"rate_rps": 8.0}, 9)
    blocks = [tr.ramp_block(k) for k in range(3)]
    assert all(0.0 <= r.due_s < 10.0 for b in blocks for r in b)
    assert [r.due_s for r in blocks[0]] != [r.due_s for r in blocks[1]]
    assert abs(sum(len(b) for b in blocks) / 30.0 - 8.0) < 2.5
    again = T.Traffic(chat, {"rate_rps": 8.0}, 9).ramp_block(1)
    assert [(r.due_s, r.prompt) for r in again] == [(r.due_s, r.prompt) for r in blocks[1]]


def test_a_window_opened_anew_sends_the_same_sizes_in_other_text():
    """After a window is given up the schedule starts over: the same sizes
    at the same instants, and no prompt of the first attempt again (the
    prefix cache would serve it from the pages the first attempt left)."""
    spec = mix("chat-open")
    first = T.Traffic(spec, {"rate_rps": 4.0}, 11).open_schedule(30.0)
    again = T.Traffic(spec, {"rate_rps": 4.0}, 11).open_schedule(30.0, attempt=1)
    assert [(r.due_s, r.prompt_tokens, r.out_tokens) for r in first] == \
           [(r.due_s, r.prompt_tokens, r.out_tokens) for r in again]
    assert not {r.prompt for r in first} & {r.prompt for r in again}
    assert [r.prompt for r in first] == \
           [r.prompt for r in T.Traffic(spec, {"rate_rps": 4.0}, 11).open_schedule(30.0)]
