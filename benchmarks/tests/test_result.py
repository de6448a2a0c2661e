"""The end of a run, driven without a chip or an engine: ``Run.result``
over hand-made samples says ``correct`` for a sound window and ``false``
for each way the timed path can be broken underneath (a token lost or
altered where it is produced, a served token that is not the reference's,
a program compiled inside the window, nothing served at all), and prints
every number compared beside its limit as the last lines of stderr."""

import json
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks.harness import Run
from benchmarks.metrics import Sample

CELL = "mistral-7b-v0.3-int8.batch-closed"
SOUND = {"positions": 128, "compared": 40, "equal": 40, "margin": 0.25, "min_compared": 8,
         "finite": True, "ok": True}


def served(due, budget=8, lost=0, text_ok=True):
    s = Sample(due=due, sent=due, budget=budget, prompt_tokens=100)
    s.events = [(due + 0.2, 1), (due + 0.6, budget - 1 - lost)]
    s.done, s.text_ok = due + 0.7, text_ok
    return s


def a_run(samples, compile_at=None):
    cell = M.resolve_cell(M.load_manifest(M.ROOT), CELL, M.ROOT)
    run = Run(cell, seed=3, seconds=10.0, trace=False, rehearse=True, t_process=0.0)
    run.devices = [SimpleNamespace(platform="cpu", device_kind="cpu", memory_stats=lambda: {})]
    run.t0, run.t_end = 100.0, 110.0
    run.everything = list(samples)
    run.samples = [s for s in samples if run.t0 <= s.due < run.t_end]
    if compile_at is not None:
        run.compiles.stamps.append((compile_at, 0.5, "jit(decode)"))
    return run


@pytest.mark.parametrize("case,agree,samples,compile_at,correct", [
    ("sound", SOUND, [served(101.0), served(105.0)], None, True),
    ("a compile before the window is set-up", SOUND, [served(101.0)], 99.0, True),
    ("a token lost on the way", SOUND, [served(101.0), served(105.0, lost=1)], None, False),
    ("token events that do not add up to the final text", SOUND,
     [served(101.0, text_ok=False)], None, False),
    ("a served token that is not the reference's", dict(SOUND, equal=39, ok=False),
     [served(101.0)], None, False),
    ("too few positions decided", dict(SOUND, compared=7, equal=7, ok=False),
     [served(101.0)], None, False),
    ("a logit that is not finite", dict(SOUND, finite=False, ok=False), [served(101.0)], None,
     False),
    ("a short generation", {"ok": False, "why": "short generation"}, [served(101.0)], None, False),
    ("a program compiled inside the window", SOUND, [served(101.0)], 104.0, False),
    ("nothing due in the window", SOUND, [served(50.0)], None, False),
])
def test_correct_is_false_for_each_way_the_timed_path_breaks(case, agree, samples, compile_at,
                                                            correct, capsys):
    out = a_run(samples, compile_at).result(agree, setup_s=90.0, drained_s=1.0)
    assert out["correct"] is correct, case
    assert out["attempted"] == sum(1 for s in samples if 100.0 <= s.due < 110.0)
    captured = capsys.readouterr()
    err = captured.err.strip().splitlines()
    assert err[-1] == f"benchmarks/run.py: correct={str(correct).lower()}"
    rows = [line for line in err if "(limit " in line]
    assert len(rows) == 7 and all(" ok  " in r or " FAIL " in r for r in rows)
    assert any(" FAIL " in r for r in rows) is not correct
    window = next(json.loads(line) for line in captured.out.splitlines()
                  if line.startswith('{"phase": "window"'))
    assert bool(window["faults"]) is not correct
