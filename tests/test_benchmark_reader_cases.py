"""Tier-1 collects the readers' own cases (ISSUE 52).

``pytest tests/`` does not collect ``benchmarks/tests/``, so cases there count
for nothing in the driver's run.  This file imports the cases of the stream
readers (``benchmarks/tests/test_stream_readers.py``) and of the traced
window (``benchmarks/tests/test_traced_window.py``) under their own names, so
the driver's command runs them as they stand: none is edited to fit, and a
case added to either file is collected here with no edit.
"""

from benchmarks.tests.test_stream_readers import *  # noqa: F401,F403
from benchmarks.tests.test_traced_window import *  # noqa: F401,F403
