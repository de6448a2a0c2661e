"""``observability/devtrace.py``: the reduction over hand-made events, the
wire reader over a hand-made ``.xplane.pb``, the capture's refusals, and
the ``/profile`` endpoint (ISSUE 24)."""

import asyncio
import json
import re
from pathlib import Path

import pytest

from calfkit_tpu.observability import devtrace
from calfkit_tpu.observability.http import MetricsServer

TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"
US = 1_000  # ns


def op(name, scope, start_us, dur_us, plane=TPU0):
    return (plane, name, scope, start_us * US, dur_us * US)


# one dispatch: a while of two steps, then a finalize; 10 us of idling between
OPS = [
    op("%while.1", "decode_loop", 0, 100),
    op("%fusion.1", "decode_loop/gather_window", 0, 30),
    op("%fusion.2", "decode_loop/attention", 30, 20),
    op("%fusion.3", "decode_loop/mlp/dequant", 50, 40),  # 10 us of the while's own follow
    op("%copy.9", "", 110, 5),
    op("%fusion.4", "finalize/kv_write", 115, 15),
]
MODULES = [(TPU0, "jit_ragged_paged(123)", 0, 100 * US), (TPU0, "jit_finalize(9)", 110 * US, 20 * US)]
HOST = [("engine.sync", 90 * US, 12 * US), ("engine.fanout", 102 * US, 4 * US)]


class TestReduce:
    def test_nested_operations_are_counted_once(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=200e-6)
        assert out["devices"] == 1
        assert out["busy_s"] == pytest.approx(120e-6)  # the while + the two after it
        assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"])
        assert sum(out["scope2_s"].values()) == pytest.approx(out["busy_s"])
        assert out["idle_pct"] == pytest.approx(40.0)

    def test_scopes_at_depth_one_and_two(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=200e-6)
        assert out["scope_s"] == pytest.approx(
            {"decode_loop": 100e-6, "finalize": 15e-6, devtrace.UNSCOPED: 5e-6})
        assert out["scope2_s"] == pytest.approx({
            "decode_loop/mlp": 40e-6, "decode_loop/gather_window": 30e-6,
            "decode_loop/attention": 20e-6, "decode_loop": 10e-6,  # the while's own
            "finalize/kv_write": 15e-6, devtrace.UNSCOPED: 5e-6,
        })
        assert list(out["scope_s"])[0] == "decode_loop"  # largest first
        assert out["unscoped_pct"] == pytest.approx(100 * 5 / 120)

    def test_modules_by_family(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=200e-6)
        assert out["module_s"] == pytest.approx({"jit_ragged_paged": 100e-6, "jit_finalize": 20e-6})

    def test_gaps_are_split_by_the_phase_that_covers_them(self):
        # the gap is 100..110 us: sync covers 100..102, fanout 102..106, nothing the rest
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=200e-6)
        assert out["gap_s"] == pytest.approx(
            {"engine.fanout": 4e-6, devtrace.UNATTRIBUTED: 4e-6, "engine.sync": 2e-6})
        assert out["gap_unattributed_pct"] == pytest.approx(40.0)

    def test_devices_are_averaged_and_gaps_are_the_first_ones(self):
        second = [op("%fusion.7", "chunk_loop/qkv", 0, 60, plane=TPU1)]
        out = devtrace.reduce_trace(OPS + second, MODULES, HOST, window_s=200e-6)
        assert out["devices"] == 2
        assert out["busy_s"] == pytest.approx((120e-6 + 60e-6) / 2)
        assert out["scope_s"]["chunk_loop"] == pytest.approx(30e-6)
        assert sum(out["gap_s"].values()) == pytest.approx(10e-6)

    def test_no_device_in_the_trace(self):
        assert devtrace.reduce_trace([], [], HOST, window_s=1.0) == {"devices": 0, "window_s": 1.0}

    def test_scope_path_keeps_the_programs_names_only(self):
        assert devtrace.scope_path(
            "jit(ragged_paged)/jit(main)/decode_loop/while/body/closed_call/qkv/"
            "bsd,dnh->bsnh/dot_general:") == "decode_loop/qkv"
        assert devtrace.scope_path("jit(f)/while/body/add") == ""

    def test_every_scope_the_program_opens_is_known(self):
        root = Path(devtrace.__file__).resolve().parents[1] / "inference"
        opened = set()
        for path in root.glob("*.py"):
            opened |= set(re.findall(r'named_scope\("([^"]+)"\)', path.read_text()))
        assert opened == devtrace.SCOPES


# ---------------------------------------------------------------- the file
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    """A device plane with two operations (one scoped through a str stat,
    one through a ref stat) and a module, a host plane with one engine
    annotation and one event that is nobody's."""
    stat_md = (_field(5, _entry(1, _field(2, "tf_op")))
               + _field(5, _entry(2, _field(2, "jit(f)/chunk_loop/mlp/dot_general:"))))
    event_md = (
        _field(4, _entry(1, _field(2, "%fusion.1 = f32[8]") + _field(
            5, _field(1, 1) + _field(5, "jit(f)/decode_loop/while/body/attention/exp:"))))
        + _field(4, _entry(2, _field(2, "%fusion.2") + _field(5, _field(1, 1) + _field(7, 2))))
        + _field(4, _entry(3, _field(2, "jit_f(77)"))))
    ops = _field(2, "XLA Ops") + _field(3, 1000) + _field(
        4, _field(1, 1) + _field(2, 5_000_000) + _field(3, 2_000_000)) + _field(
        4, _field(1, 2) + _field(2, 8_000_000) + _field(3, 1_000_000))
    mods = _field(2, "XLA Modules") + _field(3, 1000) + _field(
        4, _field(1, 3) + _field(2, 5_000_000) + _field(3, 4_000_000))
    other = _field(2, "Steps") + _field(3, 1000) + _field(4, _field(1, 3) + _field(3, 9))
    device = _field(2, "/device:TPU:0") + _field(3, ops) + _field(3, mods) + _field(
        3, other) + event_md + stat_md
    host = (_field(2, "/host:CPU")
            + _field(4, _entry(1, _field(2, "engine.sync")))
            + _field(4, _entry(2, _field(2, "PjitFunction(f)")))
            + _field(3, _field(2, "python3") + _field(3, 2000) + _field(
                4, _field(1, 1) + _field(2, 1_000_000) + _field(3, 3_000_000)) + _field(
                4, _field(1, 2) + _field(2, 1_000_000) + _field(3, 1_000_000))))
    return _field(1, device) + _field(1, host)


class TestLoad:
    def test_reads_operations_scopes_modules_and_annotations(self, tmp_path):
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(_xspace())
        ops, modules, host = devtrace.read_trace(str(path))
        assert ops == [
            (TPU0, "%fusion.1 = f32[8]", "decode_loop/attention", 6000, 2000),
            (TPU0, "%fusion.2", "chunk_loop/mlp", 9000, 1000),
        ]
        assert modules == [(TPU0, "jit_f(77)", 6000, 4000)]
        assert host == [("engine.sync", 3000, 3000)]
        out = devtrace.reduce_trace(ops, modules, host, window_s=1e-5)
        assert out["scope2_s"] == pytest.approx(
            {"decode_loop/attention": 2e-6, "chunk_loop/mlp": 1e-6})


# ------------------------------------------------------------- the capture
class TestCapture:
    def test_a_second_capture_is_refused(self):
        assert devtrace._capturing.acquire(blocking=False)
        try:
            with pytest.raises(devtrace.CaptureBusy):
                devtrace.capture(0.01)
        finally:
            devtrace._capturing.release()

    @pytest.mark.parametrize("seconds", [0, -1, 61, float("nan")])
    def test_seconds_out_of_range_are_refused(self, seconds):
        with pytest.raises(ValueError):
            devtrace.capture(seconds)
        assert devtrace._capturing.acquire(blocking=False)  # and nothing is held
        devtrace._capturing.release()

    def test_someone_elses_profile_is_reported_not_raised(self, tmp_path):
        jax = pytest.importorskip("jax")
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = devtrace.capture(0.01)
        finally:
            jax.profiler.stop_trace()
        assert out["captured"] is False and "profile" in out["reason"].lower()

    def test_capture_on_a_host_without_the_device(self):
        pytest.importorskip("jax")
        out = devtrace.capture(0.05)  # the CPU lane: no /device:TPU plane
        assert out["captured"] is True and out["devices"] == 0
        assert out["window_s"] >= 0.05


async def _get(port: int, path: str) -> tuple[int, bytes]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


class TestProfileEndpoint:
    async def test_profile_returns_the_reduction(self):
        pytest.importorskip("jax")
        async with MetricsServer(port=0) as server:
            status, body = await _get(server.port, "/profile?seconds=0.05")
        assert status == 200
        assert json.loads(body)["captured"] is True

    async def test_bad_seconds_and_a_busy_capture(self):
        async with MetricsServer(port=0) as server:
            status, _ = await _get(server.port, "/profile?seconds=abc")
            assert status == 400
            status, _ = await _get(server.port, "/profile?seconds=600")
            assert status == 400
            assert devtrace._capturing.acquire(blocking=False)
            try:
                status, body = await _get(server.port, "/profile?seconds=0.01")
            finally:
                devtrace._capturing.release()
            assert status == 409 and b"already running" in body
            status, _ = await _get(server.port, "/metrics")
            assert status == 200  # the other paths are as they were
