"""FLOPs and bytes from a configuration file, against hand arithmetic."""

import json
import os

import pytest

from benchmarks import opcount
from benchmarks.manifest import load_architecture, load_peaks

arch = load_architecture("dense-gqa")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_files():
    for name in ("internlm2-1.8b", "mistral-7b-v0.3-int8"):
        c = config(name)
        D, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
        matrices = arch.weight_bytes(c) / opcount.WEIGHT_BYTES[c["precision"]["weights"]]
        assert matrices + V * D + (L * 2 * D + D) == c["parameters"]  # + embedding + norms
        assert arch.state_bytes_per_token(c) == c["hbm"]["kv_bytes_per_token"]
        assert c["hbm"]["pool_bytes"] == c["hbm"]["pool_pages"] * c["hbm"]["page_bytes"]
        assert c["runtime"]["num_kv_pages"] == c["hbm"]["pool_pages"] + 1  # + the trash page


def test_decode_step_is_bytes_bound_and_prefill_flops_bound():
    c = config("internlm2-1.8b")
    peaks = load_peaks("TPU v5 lite")
    work = arch.decode_step(c, rows=40, mean_context=500)
    assert work["bytes"] == pytest.approx(1699479552 * 2 + 98304 * 40 * 500)
    seconds, bound = opcount.least_seconds(work, peaks)
    assert bound == "bytes" and seconds == pytest.approx(work["bytes"] / 819e9)
    chunk = arch.prefill_chunk(c, rows=4, chunk=512, offset=0)
    assert opcount.least_seconds(chunk, peaks)[1] == "flops"
    # int8 halves the weight stream of the 7B configuration
    m = config("mistral-7b-v0.3-int8")
    assert arch.weight_bytes(m) == 7113539584  # a byte a matrix parameter
    assert arch.decode_step(m, 32, 400, chips=4)["flops"] * 4 == \
           arch.decode_step(m, 32, 400)["flops"]
