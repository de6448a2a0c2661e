"""meshlint self-tests (ISSUE 12).

Fixture mini-projects pin every effect and rule; the real-tree tests pin
the acceptance contract: clean tree exits 0, a seeded transitive
violation (hot root -> clean helper -> logging helper) exits 1 printing
the full call chain, and the ``scripts/lint_hotpath.py`` shim keeps the
old CI entry point working.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPTS = REPO / "scripts"
if str(SCRIPTS) not in sys.path:
    sys.path.insert(0, str(SCRIPTS))

from meshlint import Config, analyze, default_config  # noqa: E402
from meshlint.config import RequiredRoots  # noqa: E402


def make_config(tmp_path: Path, files: "dict[str, str]", **kwargs) -> Config:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    kwargs.setdefault("scan", ["pkg"])
    kwargs.setdefault("package_prefix", "pkg")
    return Config(root=tmp_path, **kwargs)


def rules_of(report) -> "set[str]":
    return {v.rule for v in report.violations}


# --------------------------------------------------------------- call graph


class TestTransitiveChains:
    def test_seeded_chain_reports_every_hop(self, tmp_path):
        """The acceptance shape: root -> clean helper -> logging helper,
        across three modules, reported as the full chain."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/a.py": """
                from calfkit_tpu.effects import hotpath
                from pkg.b import helper

                @hotpath
                def tick():
                    helper()
            """,
            "pkg/b.py": """
                from pkg.c import log_helper

                def helper():
                    log_helper()
            """,
            "pkg/c.py": """
                import logging
                logger = logging.getLogger(__name__)

                def log_helper():
                    logger.info("per-dispatch log line")
            """,
        })
        report = analyze(config)
        assert not report.ok
        [v] = [v for v in report.violations if v.rule == "hotpath"]
        assert v.effect == "LOG"
        assert [h.qname for h in v.chain] == [
            "pkg.a.tick", "pkg.b.helper", "pkg.c.log_helper",
        ]
        assert v.path == "pkg/c.py"
        rendered = report.render(chains=True)
        assert "pkg.a.tick" in rendered
        assert "pkg.b.helper" in rendered
        assert "pkg/c.py" in rendered

    def test_method_dispatch_through_self_and_local_ctor(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath

                class Helper:
                    def nap(self):
                        time.sleep(1)

                class Engine:
                    @hotpath
                    def tick(self):
                        self._go()

                    def _go(self):
                        h = Helper()
                        h.nap()
            """,
        })
        report = analyze(config)
        [v] = [v for v in report.violations if v.rule == "hotpath"]
        assert v.effect == "BLOCK"
        assert [h.qname for h in v.chain] == [
            "pkg.m.Engine.tick", "pkg.m.Engine._go", "pkg.m.Helper.nap",
        ]

    def test_conservative_name_fallback_links_dynamic_receivers(
        self, tmp_path
    ):
        """An attribute call on an untypable receiver still reaches every
        project method of that name — the over-approximation that keeps
        dynamically-dispatched helpers inside the closure."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath

                class Drafter:
                    def propose_draft(self):
                        time.sleep(1)

                class Engine:
                    @hotpath
                    def tick(self):
                        self._drafter.propose_draft()
            """,
        })
        report = analyze(config)
        assert any(
            v.rule == "hotpath" and v.effect == "BLOCK"
            for v in report.violations
        )

    def test_relative_import_in_package_init_resolves(self, tmp_path):
        """A level-1 relative import inside __init__.py resolves against
        the package ITSELF (p.q), not its parent — a mis-strip here
        silently voids coverage for any __init__-rooted chain."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": """
                from calfkit_tpu.effects import hotpath
                from .helper import log_fn

                @hotpath
                def init_root():
                    log_fn()
            """,
            "pkg/helper.py": """
                import logging
                logger = logging.getLogger(__name__)

                def log_fn():
                    logger.info("hi")
            """,
        })
        report = analyze(config)
        assert any(
            v.rule == "hotpath" and v.chain[0].qname == "pkg.init_root"
            and v.chain[-1].qname == "pkg.helper.log_fn"
            for v in report.violations
        )

    def test_spawned_coroutine_does_not_leak_into_spawner_closure(
        self, tmp_path
    ):
        """`create_task(self._bg())` builds a coroutine object; the body
        runs on the spawned task (independently rooted by the stall
        rule), so its effects must not propagate into the spawner."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio
                import logging
                from calfkit_tpu.effects import hotpath
                logger = logging.getLogger(__name__)

                class E:
                    @hotpath
                    def kick(self):
                        asyncio.create_task(self._bg())

                    async def _bg(self):
                        logger.info("background beat")
            """,
        })
        report = analyze(config)
        assert "hotpath" not in rules_of(report)

    def test_reassigned_local_drops_precise_binding(self, tmp_path):
        """`x = C(); x = unknown(); x.get()` must not keep dispatching to
        C.get — statement ORDER drives the drop law ("get" is in the
        fallback skip set, so a stale binding is the only edge source)."""
        files = {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath

                class C:
                    def get(self):
                        time.sleep(1)

                @hotpath
                def stale():
                    x = C()
                    x = unknown_factory()
                    x.get()

                @hotpath
                def precise():
                    x = C()
                    x.get()
            """,
        }
        report = analyze(make_config(tmp_path, files))
        roots = {v.chain[0].qname for v in report.violations
                 if v.rule == "hotpath"}
        assert roots == {"pkg.m.precise"}

    def test_nested_def_body_not_attributed_to_parent(self, tmp_path):
        """A jit body builder's device code must not pollute the host
        function: a nested def that is only RETURNED contributes nothing;
        one the parent CALLS does."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath

                @hotpath
                def builder():
                    def body():
                        time.sleep(1)
                    return body

                @hotpath
                def caller():
                    def body():
                        time.sleep(1)
                    body()
            """,
        })
        report = analyze(config)
        offenders = {v.chain[0].qname for v in report.violations
                     if v.rule == "hotpath"}
        assert offenders == {"pkg.m.caller"}


# ------------------------------------------------------------ effect matrix


class TestEffectMatrix:
    def test_wallclock_vs_monotonic(self, tmp_path):
        """@no_wallclock bans BOTH clock families; @hotpath bans only the
        wall clock — perf_counter is the sanctioned hot-path clock."""
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath, no_wallclock

                @no_wallclock
                def gated_metric():
                    return time.perf_counter()

                @hotpath
                def tick():
                    t = time.perf_counter()
                    return t

                @hotpath
                def bad_tick():
                    return time.time()
            """,
        })
        report = analyze(config)
        flagged = {(v.chain[0].qname, v.effect) for v in report.violations}
        assert ("pkg.m.gated_metric", "MONOTONIC") in flagged
        assert ("pkg.m.bad_tick", "WALLCLOCK") in flagged
        assert not any(q == "pkg.m.tick" for q, _ in flagged)

    def test_device_sync_and_no_log(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                from calfkit_tpu.effects import hotpath, no_log

                @hotpath
                def tick(arr):
                    return arr.block_until_ready()

                @no_log
                def quiet():
                    print("hi")
            """,
        })
        report = analyze(config)
        flagged = {(v.chain[0].qname, v.effect) for v in report.violations}
        assert ("pkg.m.tick", "DEVICE_SYNC") in flagged
        assert ("pkg.m.quiet", "LOG") in flagged

    def test_from_imported_clock_names(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                from time import monotonic
                from calfkit_tpu.effects import no_wallclock

                @no_wallclock
                def stamp():
                    return monotonic()
            """,
        })
        report = analyze(config)
        assert any(v.effect == "MONOTONIC" for v in report.violations)

    def test_hotpath_must_stay_sync(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                from calfkit_tpu.effects import hotpath

                @hotpath
                async def select():
                    return None
            """,
        })
        report = analyze(config)
        assert "hotpath-sync-shape" in rules_of(report)


# ------------------------------------------------------------- escape rules


class TestEscapeComments:
    def test_blocking_ok_waives_site_for_every_root(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import hotpath

                @hotpath
                def tick():
                    helper()

                def helper():
                    # blocking-ok: first-dispatch jit build, cached after
                    time.sleep(0)
            """,
        })
        assert analyze(config).ok

    def test_comment_block_above_counts(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import no_wallclock

                @no_wallclock
                def stamp():
                    # this site is deliberate:
                    # wallclock-ok: report capture block, stripped by gate
                    return time.time()
            """,
        })
        assert analyze(config).ok

    def test_unrelated_comment_does_not_waive(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import time
                from calfkit_tpu.effects import no_block

                @no_block
                def f():
                    time.sleep(1)  # TODO fix later
            """,
        })
        assert not analyze(config).ok


# ----------------------------------------------------- event-loop stall rule


class TestAsyncStall:
    FILES = {
        "pkg/__init__.py": "",
        "pkg/m.py": """
            import asyncio
            import time

            def blocking_helper():
                time.sleep(1)

            async def stalls():
                blocking_helper()

            async def offloads():
                await asyncio.to_thread(blocking_helper)
        """,
    }

    def test_direct_transitive_block_flagged(self, tmp_path):
        report = analyze(make_config(tmp_path, self.FILES))
        stalls = [v for v in report.violations if v.rule == "async-stall"]
        assert len(stalls) == 1
        assert stalls[0].chain[0].qname == "pkg.m.stalls"

    def test_to_thread_handoff_is_legal(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio
                import time

                def blocking_helper():
                    time.sleep(1)

                async def offloads():
                    await asyncio.to_thread(blocking_helper)
            """,
        })
        assert "async-stall" not in rules_of(analyze(config))

    def test_stall_outside_package_prefix_ignored(self, tmp_path):
        config = make_config(tmp_path, self.FILES,
                             package_prefix="otherpkg")
        assert "async-stall" not in rules_of(analyze(config))


# ------------------------------------------------------- await atomicity


class TestAwaitAtomicity:
    def test_read_await_write_flagged(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio

                class S:
                    async def bump(self):
                        n = self._count
                        await asyncio.sleep(0)
                        self._count = n + 1
            """,
        })
        report = analyze(config)
        [v] = [v for v in report.violations if v.rule == "await-atomicity"]
        assert v.detail == "self._count"

    def test_augassign_after_await_is_fresh(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio

                class S:
                    async def bump(self):
                        if self._count > 0:
                            await asyncio.sleep(0)
                            self._count += 1
            """,
        })
        assert "await-atomicity" not in rules_of(analyze(config))

    def test_reread_after_await_is_fresh(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio

                class S:
                    async def bump(self):
                        n = self._count
                        await asyncio.sleep(0)
                        self._count = self._count + 1
            """,
        })
        assert "await-atomicity" not in rules_of(analyze(config))

    def test_atomicity_ok_annotation_honored(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio

                class S:
                    async def start(self):
                        if self._started:
                            return
                        await asyncio.sleep(0)
                        # atomicity-ok: double-checked under the lock
                        self._started = True
            """,
        })
        assert "await-atomicity" not in rules_of(analyze(config))

    def test_write_with_no_prior_read_not_flagged(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                import asyncio

                class S:
                    async def set(self):
                        await asyncio.sleep(0)
                        self._done = True
            """,
        })
        assert "await-atomicity" not in rules_of(analyze(config))


# ------------------------------------------------------ migrated rules


class TestUnboundedQueues:
    def make(self, tmp_path, body):
        return make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/q.py": "import asyncio\nfrom collections import deque\n"
                        "from dataclasses import dataclass, field\n" + body,
        }, queue_scope=["pkg.q"])

    def test_unjustified_flagged_justified_waived(self, tmp_path):
        report = analyze(self.make(tmp_path, textwrap.dedent("""
            BAD = asyncio.Queue()
            # unbounded-ok: drained by the per-tick reaper
            GOOD = asyncio.Queue()
        """)))
        queue_violations = [v for v in report.violations
                            if v.rule == "unbounded-queue"]
        assert len(queue_violations) == 1

    def test_bound_semantics(self, tmp_path):
        """maxsize<=0 is UNLIMITED for Queue kinds; deque(maxlen=0) is a
        real bound — the exact lore from the old lint."""
        report = analyze(self.make(tmp_path, textwrap.dedent("""
            A = asyncio.Queue(maxsize=8)     # bounded
            B = deque(maxlen=0)              # bounded (always empty)
            C = asyncio.Queue(0)             # UNLIMITED -> flagged
            D = deque()                      # unbounded -> flagged
        """)))
        lines = sorted(v.lineno for v in report.violations
                       if v.rule == "unbounded-queue")
        assert len(lines) == 2

    def test_default_factory_flagged(self, tmp_path):
        report = analyze(self.make(tmp_path, textwrap.dedent("""
            @dataclass
            class S:
                buf: deque = field(default_factory=deque)
        """)))
        assert any(v.rule == "unbounded-queue" and
                   "default_factory" in v.detail
                   for v in report.violations)

    def test_out_of_scope_module_ignored(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/other.py": "import asyncio\nQ = asyncio.Queue()\n",
        }, queue_scope=["pkg.q"])
        assert "unbounded-queue" not in rules_of(analyze(config))


class TestSimWallclock:
    def test_direct_read_flagged_and_waivable(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/sim/__init__.py": "",
            "pkg/sim/clock.py": """
                import time

                def bad():
                    return time.monotonic()

                def ok():
                    # wallclock-ok: real-time chaos helper, not scenario
                    return time.monotonic()
            """,
        }, sim_scope="pkg.sim")
        report = analyze(config)
        sim = [v for v in report.violations if v.rule == "sim-wallclock"]
        assert len(sim) == 1
        assert sim[0].detail == "time.monotonic()"


class TestFlightrecRules:
    def test_journal_append_formatting_flagged(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/engine.py": """
                class E:
                    def tick(self):
                        self._journal.append(1, f"row {self}")
                        self._journal.append(2, "precomputed", 3)
            """,
        }, journal_module="pkg.engine")
        report = analyze(config)
        sites = [v for v in report.violations
                 if v.rule == "journal-append-site"]
        assert len(sites) == 1
        assert sites[0].detail == "f-string"

    def test_append_body_rule_and_loud_miss(self, tmp_path):
        config = make_config(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/frec.py": """
                class FlightRecorder:
                    def append(self, code):
                        self._ring.append(("%s" % code,))
            """,
        }, flightrec_append=("pkg.frec", "FlightRecorder", "append"))
        report = analyze(config)
        assert any(v.rule == "flightrec-append" and "%-formatting" in v.detail
                   for v in report.violations)
        # loud-miss: a rename must break the lint, not silently pass
        gone = make_config(tmp_path, {},
                           flightrec_append=("pkg.frec", "FlightRecorder",
                                             "renamed_append"))
        assert any(v.effect == "MISSING"
                   for v in analyze(gone).violations)


class TestCoverage:
    def test_root_floor_enforced(self, tmp_path):
        files = {
            "pkg/__init__.py": "",
            "pkg/m.py": """
                from calfkit_tpu.effects import hotpath

                @hotpath
                def tick():
                    return None
            """,
        }
        short = make_config(tmp_path, files, required_roots=[
            RequiredRoots("pkg.m", "hotpath", 2, "closure must stay rooted"),
        ])
        report = analyze(short)
        assert "root-coverage" in rules_of(report)
        met = make_config(tmp_path, files, required_roots=[
            RequiredRoots("pkg.m", "hotpath", 1, ""),
        ])
        assert "root-coverage" not in rules_of(analyze(met))


# ----------------------------------------------------------- the real tree


def _seed_violation(root: Path) -> None:
    engine = root / "calfkit_tpu" / "inference" / "engine.py"
    engine.write_text(engine.read_text() + textwrap.dedent("""


        @hotpath
        def _meshlint_seeded_root():
            _meshlint_seeded_clean_helper()


        def _meshlint_seeded_clean_helper():
            _meshlint_seeded_logging_helper()


        def _meshlint_seeded_logging_helper():
            logger.info("seeded transitive violation")
    """))


@pytest.fixture(scope="module")
def tree_copy(tmp_path_factory):
    """A copy of everything meshlint scans, with a seeded hot-root ->
    clean-helper -> logging-helper chain appended to engine.py."""
    root = tmp_path_factory.mktemp("seeded-tree")
    shutil.copytree(
        REPO / "calfkit_tpu", root / "calfkit_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    (root / "scripts").mkdir()
    shutil.copy(REPO / "scripts" / "perf_gate.py",
                root / "scripts" / "perf_gate.py")
    _seed_violation(root)
    return root


class TestRealTree:
    def test_clean_tree_is_clean(self):
        report = analyze(default_config(REPO))
        assert report.ok, report.render(chains=True)
        # the closure actually covers the load-bearing roots
        assert report.stats["hotpath"] >= 20
        assert report.stats["no_wallclock"] >= 2
        assert report.stats["async_defs"] > 100

    def test_seeded_violation_exits_1_with_full_chain(
        self, tree_copy, tmp_path
    ):
        out_json = tmp_path / "meshlint.json"
        proc = subprocess.run(
            [sys.executable, "-m", "meshlint", "--root", str(tree_copy),
             "--chains", "--json", str(out_json)],
            capture_output=True, text=True,
            env={"PYTHONPATH": str(SCRIPTS), "PATH": "/usr/bin:/bin"},
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        # the full chain, every hop, with the offending file:line
        assert "_meshlint_seeded_root" in proc.stdout
        assert "_meshlint_seeded_clean_helper" in proc.stdout
        assert "_meshlint_seeded_logging_helper" in proc.stdout
        assert "logger.info()" in proc.stdout
        document = json.loads(out_json.read_text())
        assert document["ok"] is False
        [v] = [v for v in document["violations"]
               if v["rule"] == "hotpath"]
        assert [h["qname"].rsplit(".", 1)[-1] for h in v["chain"]] == [
            "_meshlint_seeded_root",
            "_meshlint_seeded_clean_helper",
            "_meshlint_seeded_logging_helper",
        ]
        assert v["path"].endswith("engine.py")
        assert v["lineno"] > 0
        # each non-root hop names the file its call line lives in
        for hop in v["chain"][1:]:
            assert hop["call_path"].endswith("engine.py")

    def test_shim_exits_0_on_clean_tree(self):
        proc = subprocess.run(
            [sys.executable, str(SCRIPTS / "lint_hotpath.py")],
            capture_output=True, text=True, cwd=str(REPO),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "meshlint: clean" in proc.stdout

    def test_shim_exits_1_on_seeded_violation(self, tree_copy):
        proc = subprocess.run(
            [sys.executable, str(SCRIPTS / "lint_hotpath.py"),
             "--root", str(tree_copy)],
            capture_output=True, text=True, cwd=str(REPO),
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "_meshlint_seeded_clean_helper" in proc.stdout
