"""The loader: what it refuses, and that a configuration, a mix, a cell and
a per-layer metric are each added by new files and new entries alone."""

import json
import os
import shutil

import pytest

from benchmarks import manifest as M

ROOT = M.ROOT


def test_the_committed_manifest_resolves_every_cell():
    man = M.load_manifest(ROOT)
    assert man["command"] == ["python3", "benchmarks/run.py"] and man["paths"] == ["benchmarks"]
    for row in man["workloads"]:
        cell = M.resolve_cell(man, row["name"], ROOT)
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer and all(m.moves in names for m in cell.per_layer)
        assert all(callable(m.read) for m in cell.per_layer)
    layers = {m["layer"] for m in man["per_layer"]}
    assert layers <= {"client and mesh", "node and agent", "admission and batching",
                      "KV pages", "model step", "kernels", "device"}
    assert all(0 < m["bound"] <= 0.1 for m in man["end_to_end"])


@pytest.mark.parametrize("bad", ["", "two words", "a,b", "a/b", "x" * 65, "µs", "-lead"])
def test_names_obey_the_character_rules(bad):
    with pytest.raises(M.ManifestError):
        M.check_name(bad, "name")


@pytest.mark.parametrize("unit,ok", [("ms", True), ("tokens/s/chip", True), ("%", True),
                                     ("GB", True), ("tokens per second", False),
                                     ("µs", False), ("", False), ("x" * 17, False)])
def test_units_obey_the_character_rules(unit, ok):
    if ok:
        assert M.check_unit(unit, "m") == unit
    else:
        with pytest.raises(M.ManifestError):
            M.check_unit(unit, "m")


def test_every_name_and_unit_in_the_manifest_is_legal():
    man = M.load_manifest(ROOT)
    for entry in man["configs"] + man["workloads"] + man["end_to_end"] + man["per_layer"]:
        M.check_name(entry["name"], "entry")
    for w in man["workloads"]:
        M.check_name(w["traffic"], "traffic")
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(json.dumps(man)) < 64 * 1024


def test_unknown_device_kind_is_an_error():
    assert M.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(M.ManifestError, match="unknown device_kind"):
        M.load_peaks("TPU v5")
    with pytest.raises(M.ManifestError):
        M.load_peaks("cpu")


@pytest.fixture()
def copy(tmp_path):
    """A copy of the benchmark (manifest and data files) to add things to."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _rewrite(root, edit):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    edit(man)
    with open(path, "w") as f:
        json.dump(man, f)
    return M.load_manifest(str(root))


def test_unknown_metric_reader_and_workload_are_refused(copy):
    man = M.load_manifest(str(copy))
    with pytest.raises(M.ManifestError, match="unknown workload"):
        M.resolve_cell(man, "no-such.cell", str(copy))

    def add_metric(man):
        man["per_layer"].append({"name": "nothing_reads_me", "unit": "ms", "better": "lower",
                                 "source": "program_span", "layer": "device",
                                 "moves": "tpot_p95_ms"})
    man = _rewrite(copy, add_metric)
    with pytest.raises(M.ManifestError, match="unknown metric 'nothing_reads_me'"):
        M.resolve_cell(man, man["workloads"][0]["name"], str(copy))
    spec = {"name": "nothing_reads_me", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "device", "moves": "tpot_p95_ms",
            "reader": "no_such_reader"}
    (copy / "benchmarks" / "layer_metrics" / "nothing_reads_me.json").write_text(json.dumps(spec))
    with pytest.raises(M.ManifestError, match="unknown reader 'no_such_reader'"):
        M.resolve_cell(man, man["workloads"][0]["name"], str(copy))

    def bad_moves(man):
        man["per_layer"][-1]["moves"] = "not_a_metric"
    with pytest.raises(M.ManifestError, match="no end-to-end metric"):
        _rewrite(copy, bad_moves)


def test_a_metric_must_move_something_its_cell_reports(copy):
    def edit(man):  # tpot_p95_ms is taken away from the cell; its layer metrics stay
        for m in man["end_to_end"]:
            if m["name"] == "tpot_p95_ms":
                m["workloads"] = ["some-other.cell"]
    man = _rewrite(copy, edit)
    with pytest.raises(M.ManifestError, match="does not report"):
        M.resolve_cell(man, man["workloads"][0]["name"], str(copy))


def test_config_mix_cell_and_metric_are_added_by_new_files_alone(copy):
    """What benchmarks/README.md describes, done in a temporary copy: four
    new files and four new entries, no file that was there edited."""
    b = copy / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    config = json.loads((b / "configs" / "internlm2-1.8b.json").read_text())
    config.update(name="tiny-new", num_hidden_layers=12)
    (b / "configs" / "tiny-new.json").write_text(json.dumps(config))
    mix = json.loads((b / "traffic" / "chat-open.json").read_text())
    mix.update(name="chat-long", prompt_tokens=dict(mix["prompt_tokens"], median=1024))
    (b / "traffic" / "chat-long.json").write_text(json.dumps(mix))
    (b / "cells" / "tiny-new.chat-long.json").write_text(json.dumps({"rate_rps": 2.5}))
    (b / "layer_metrics" / "first_event_tokens.json").write_text(json.dumps({
        "name": "first_event_tokens", "unit": "tokens", "better": "lower",
        "source": "host_clock", "layer": "client and mesh", "moves": "tpot_p95_ms",
        "reader": "first_event_tokens"}))
    (b / "readers" / "first_event_tokens.py").write_text(
        "def read(ctx):\n"
        "    got = [s.events[0][1] for s in ctx.samples if s.events]\n"
        "    return sum(got) / len(got) if got else None\n")

    def edit(man):
        man["configs"].append({"name": "tiny-new", "source": "https://example.org/tiny",
                               "file": "benchmarks/configs/tiny-new.json",
                               "reduced": ["num_hidden_layers"], "why": "a test"})
        man["workloads"].append({"name": "tiny-new.chat-long", "config": "tiny-new",
                                 "traffic": "chat-long", "chips": 1, "why": "a test"})
        for m in man["end_to_end"]:
            if "workloads" in m and m["name"].startswith("ttft"):
                m["workloads"].append("tiny-new.chat-long")
        man["per_layer"].append({"name": "first_event_tokens", "unit": "tokens",
                                 "better": "lower", "source": "host_clock",
                                 "layer": "client and mesh", "moves": "tpot_p95_ms",
                                 "workloads": ["tiny-new.chat-long"]})
    man = _rewrite(copy, edit)
    cell = M.resolve_cell(man, "tiny-new.chat-long", str(copy))
    assert cell.config["num_hidden_layers"] == 12 and cell.params == {"rate_rps": 2.5}
    assert cell.traffic["prompt_tokens"]["median"] == 1024
    assert "first_event_tokens" in {m.name for m in cell.per_layer}

    class Ctx:
        samples = [type("S", (), {"events": [(0.0, 1), (0.1, 4)]})()]
    reader = next(m.read for m in cell.per_layer if m.name == "first_event_tokens")
    assert reader(Ctx) == 1.0
    assert all(p.read_bytes() == data for p, data in before.items())  # nothing edited
    # the old cells still resolve, and do not report the new metric
    old = M.resolve_cell(man, man["workloads"][0]["name"], str(copy))
    assert "first_event_tokens" not in {m.name for m in old.per_layer}

    from benchmarks.traffic import Traffic
    reqs = Traffic(cell.traffic, cell.params, 9).open_schedule(40.0)
    assert 60 <= len(reqs) <= 140  # 2.5 requests/s for 40 s, from data alone


def test_metrics_with_a_file_and_no_entry_are_recorded_only():
    man = M.load_manifest(ROOT)
    for row in man["workloads"]:
        cell = M.resolve_cell(man, row["name"], ROOT)
        others = M.unregistered(cell, ROOT)
        named = {m.name for m in cell.per_layer}
        assert named.isdisjoint(m.name for m in others)
        files = {f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmarks", "layer_metrics"))}
        assert named | {m.name for m in others} == files
        assert all(callable(m.read) and m.moves for m in others)


def test_agreement_needs_enough_decided_positions():
    import numpy as np

    from benchmarks.reference import agreement

    prompts, outputs = [[5, 6, 7]], [[1, 2, 3, 4]]
    def fake(gaps):
        def forward(params, config, tokens, lens):
            arg = np.zeros(tokens.shape, np.int32)
            arg[0, 2:6] = outputs[0]
            gap = np.zeros(tokens.shape, np.float32)
            gap[0, 2:6] = gaps
            return arg, gap
        return forward
    forward = fake([1.0, 1.0, 1.0, 0.1])
    got = agreement(forward, None, None, prompts, outputs, 0.25, min_compared=3)
    assert got["compared"] == 3 and got["ok"]
    assert not agreement(forward, None, None, prompts, outputs, 0.25, min_compared=4)["ok"]


TOY = '''"""Architecture toy: logits = embed[token] @ head, no mixing of positions."""
import numpy as np


def model(config, rehearse):
    return dict(vocab=config["vocab_size"], width=config["hidden_size"]), None


def params(model, runtime, mesh, seed):
    rng = np.random.default_rng(seed)
    return {"embed": rng.standard_normal((model["vocab"], model["width"])),
            "head": rng.standard_normal((model["width"], model["vocab"]))}


def forward_top2(params, model, tokens, lens):
    logits = params["embed"][tokens] @ params["head"]
    order = np.sort(logits, axis=-1)
    return logits.argmax(-1), order[..., -1] - order[..., -2]


def weight_bytes(config):
    return 4.0 * config["hidden_size"] * config["vocab_size"]


def state_bytes_per_token(config):
    return 0.0


def decode_step(config, rows, mean_context, chips=1):
    return {"flops": 2.0 * rows * weight_bytes(config) / 4 / chips,
            "bytes": weight_bytes(config) / chips}


def prefill_chunk(config, rows, chunk, offset, chips=1):
    return decode_step(config, rows * chunk, 0, chips)
'''


def test_an_architecture_is_added_by_new_files_alone(copy):
    """An architecture file, a configuration that names it and a cell, in
    a temporary copy: it resolves, the shared margin rule runs through its
    reference, the shared roofline through its counts; no file that was
    there is touched, and a file that lacks a function is refused."""
    import numpy as np

    from benchmarks.opcount import least_seconds
    from benchmarks.reference import agreement

    b = copy / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "architectures" / "toy.py").write_text(TOY)
    (b / "configs" / "toy-two-matrix.json").write_text(json.dumps({
        "name": "toy-two-matrix", "architecture": "toy", "vocab_size": 50, "hidden_size": 8}))
    (b / "cells" / "toy-two-matrix.batch-closed.json").write_text(json.dumps({"callers": 2}))

    def edit(man):
        man["configs"].append({"name": "toy-two-matrix", "source": "https://example.org/toy",
                               "file": "benchmarks/configs/toy-two-matrix.json",
                               "reduced": [], "why": "a test"})
        man["workloads"].append({"name": "toy-two-matrix.batch-closed",
                                 "config": "toy-two-matrix", "traffic": "batch-closed",
                                 "chips": 1, "why": "a test"})
    man = _rewrite(copy, edit)
    cell = M.resolve_cell(man, "toy-two-matrix.batch-closed", str(copy))
    arch = cell.arch
    assert arch.__file__ == str(b / "architectures" / "toy.py")
    model, _ = arch.model(cell.config, False)
    params = arch.params(model, None, None, 7)
    prompts = [[3, 4, 5], [6, 7]]
    outputs = []  # "the engine": greedy through the same two matrices
    for p in prompts:
        arg, _ = arch.forward_top2(params, model, np.asarray([[p[-1], 0, 0]]), None)
        first = int(arg[0, 0])
        second = int(arch.forward_top2(params, model, np.asarray([[first]]), None)[0][0, 0])
        outputs.append([first, second])
    got = agreement(arch.forward_top2, params, model, prompts, outputs, 0.0, min_compared=4)
    assert got["ok"] and got["compared"] == got["equal"] == 4
    outputs[1][1] = (outputs[1][1] + 1) % 50  # one served token altered: not the reference's
    assert not agreement(arch.forward_top2, params, model, prompts, outputs, 0.0, 4)["ok"]
    seconds, bound = least_seconds(arch.decode_step(cell.config, 4, 100), M.load_peaks("TPU v5 lite"))
    assert bound == "bytes" and seconds == pytest.approx(1600 / 819e9)
    assert all(p.read_bytes() == data for p, data in before.items())  # nothing edited
    # the cells that were there still resolve, through the architecture no file of theirs names
    old = M.resolve_cell(man, man["workloads"][0]["name"], str(copy))
    assert old.arch.__file__.endswith("dense-gqa.py") and "architecture" not in old.config

    (b / "architectures" / "toy.py").write_text(TOY.replace("def prefill_chunk", "def prefil"))
    with pytest.raises(M.ManifestError, match="architecture 'toy' lacks prefill_chunk"):
        M.resolve_cell(man, "toy-two-matrix.batch-closed", str(copy))
    (b / "architectures" / "toy.py").unlink()
    with pytest.raises(M.ManifestError, match="unknown architecture 'toy'"):
        M.resolve_cell(man, "toy-two-matrix.batch-closed", str(copy))


def test_every_architecture_loads_and_the_yardstick_has_not_moved():
    """What tier-1 cannot hold while only the benchmark's own directories
    may change (PERF.md, Open questions): every architecture file loads
    whole, and the bytes a Mistral step streams (the int8 matrices and
    head: what dispatch_roofline has divided by since PR 23) stand."""
    here = os.path.join(ROOT, "benchmarks")
    names = [f[:-3] for f in os.listdir(os.path.join(here, "architectures")) if f.endswith(".py")]
    assert M.DEFAULT_ARCHITECTURE in names
    for name in names:
        M.load_architecture(name)
    man = M.load_manifest(ROOT)
    cell = M.resolve_cell(man, "mistral-7b-v0.3-int8.batch-closed", ROOT)
    assert cell.arch.weight_bytes(cell.config) == 7_113_539_584
    assert cell.arch.state_bytes_per_token(cell.config) == 131_072
