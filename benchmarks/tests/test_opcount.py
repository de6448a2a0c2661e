"""FLOPs and bytes from a configuration file, against hand arithmetic."""

import json
import os

import pytest

from benchmarks import opcount
from benchmarks.manifest import load_peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_files():
    for name in ("internlm2-1.8b", "mistral-7b-v0.3-int8"):
        c = config(name)
        s = opcount.sizes(c)
        norms = s["L"] * 2 * s["D"] + s["D"]
        assert s["matmul_params"] + s["embed_params"] + norms == c["parameters"]
        assert opcount.kv_bytes_per_token(c) == c["hbm"]["kv_bytes_per_token"]
        assert c["hbm"]["pool_bytes"] == c["hbm"]["pool_pages"] * c["hbm"]["page_bytes"]
        assert c["runtime"]["num_kv_pages"] == c["hbm"]["pool_pages"] + 1  # + the trash page


def test_decode_step_is_bytes_bound_and_prefill_flops_bound():
    c = config("internlm2-1.8b")
    peaks = load_peaks("TPU v5 lite")
    work = opcount.decode_step(c, rows=40, mean_context=500)
    assert work["bytes"] == pytest.approx(1699479552 * 2 + 98304 * 40 * 500)
    seconds, bound = opcount.least_seconds(work, peaks)
    assert bound == "bytes" and seconds == pytest.approx(work["bytes"] / 819e9)
    chunk = opcount.prefill_chunk(c, rows=4, chunk=512, offset=0)
    assert opcount.least_seconds(chunk, peaks)[1] == "flops"
    # int8 halves the weight stream of the 7B configuration
    m = config("mistral-7b-v0.3-int8")
    assert opcount.weight_bytes(m) == opcount.sizes(m)["matmul_params"]
    assert opcount.decode_step(m, 32, 400, chips=4)["flops"] * 4 == \
           opcount.decode_step(m, 32, 400)["flops"]
