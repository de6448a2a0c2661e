"""The fleet simulator + perf gate (ISSUE 11).

Four layers of coverage:

1. **seams** — the virtual event clock (ordering, ties, advance
   semantics), deterministic id minting, the scenario DSL (arrival
   processes, diurnal curve shape, scaling laws, check evaluation).
2. **runner** — one small scenario through the REAL
   mesh→worker→router path: completion, routing spread, prefix model,
   scripted kill/heal, lease churn against the real compacted table.
3. **determinism** — the acceptance law: the same scenario twice with
   the same seed is BYTE-identical (modulo the capture block); a
   different seed still passes every verdict.  The full pinned suite
   version is marked ``slow`` (CI's offline lane); a single-scenario
   version stays in tier-1.
4. **the gate** — ``scripts/perf_gate.py`` logic: baseline round-trip,
   tolerance bands, the seeded-regression seam (a worst-loaded policy
   MUST trip the gate), and the ``ck sim`` renderer.
"""

import asyncio
import importlib.util
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from calfkit_tpu.sim import (  # noqa: E402
    Check,
    LeaseChurn,
    LoadPhase,
    ReplicaEvent,
    Scenario,
    ServiceSpec,
    SimReport,
    SimRunner,
    TenantSpec,
    VirtualClock,
    deterministic_ids,
    diurnal_phases,
    strip_capture,
)
from calfkit_tpu.sim.report import flatten_metrics, metric_at, percentile  # noqa: E402
from calfkit_tpu.sim.suite import PINNED_SUITE, SUITE_NAME, scaled_suite  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_perf_gate():
    """Import scripts/perf_gate.py WITHOUT its argv/re-exec main path."""
    os.environ.setdefault("PYTHONHASHSEED", "0")
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "scripts", "perf_gate.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_SMOKE_CACHE: dict = {}


def smoke_report():
    """One shared SMOKE run for every test that only READS a report —
    the suite re-runs it fresh only where a second, independent run is
    the point (the determinism oracle).  Keeps tier-1 cost flat."""
    if "report" not in _SMOKE_CACHE:
        _SMOKE_CACHE["report"] = asyncio.run(SimRunner(SMOKE).run())
    return _SMOKE_CACHE["report"]


SMOKE = Scenario(
    name="smoke",
    replicas=6,
    seed=5,
    phases=(LoadPhase(duration_s=30.0, rate_rps=4.0),),
    service=ServiceSpec(base_s=0.5, per_token_s=0.02, slots=2),
    tenants=(TenantSpec("t0", sessions=3), TenantSpec("t1", sessions=3)),
    checks=(
        Check("all_complete", "requests.completion_ratio", "==", 1.0),
        Check("no_faults", "requests.failed", "==", 0.0),
    ),
    gated=("requests.completed",),
)


# ---------------------------------------------------------------- seams
class TestVirtualEventClock:
    def test_schedule_fires_in_time_then_insertion_order(self):
        clock = VirtualClock(1000.0)
        fired = []
        clock.schedule(1002.0, lambda: fired.append("b"))
        clock.schedule(1001.0, lambda: fired.append("a"))
        clock.schedule(1002.0, lambda: fired.append("c"))  # tie: after b
        clock.advance(5.0)
        assert fired == ["a", "b", "c"]
        assert clock.now == 1005.0

    def test_callback_sees_its_own_timestamp(self):
        clock = VirtualClock(0.0)
        seen = []
        clock.schedule(3.0, lambda: seen.append(clock.now))
        clock.schedule(7.0, lambda: seen.append(clock.now))
        clock.advance(10.0)
        assert seen == [3.0, 7.0]

    def test_callbacks_can_schedule_relative_work(self):
        clock = VirtualClock(0.0)
        fired = []

        def first():
            fired.append(clock.now)
            clock.schedule(clock.now + 2.0, lambda: fired.append(clock.now))

        clock.schedule(1.0, first)
        clock.advance(10.0)
        assert fired == [1.0, 3.0]

    def test_advance_to_next_and_past_scheduling_clamps(self):
        clock = VirtualClock(100.0)
        fired = []
        clock.schedule(50.0, lambda: fired.append("past"))  # clamped to now
        assert clock.next_event_at == 100.0
        assert clock.advance_to_next() is True
        assert fired == ["past"]
        assert clock.advance_to_next() is False


class TestDeterministicIds:
    def test_seeded_and_restored(self):
        import uuid

        with deterministic_ids(9):
            a = [uuid.uuid4() for _ in range(3)]
        with deterministic_ids(9):
            b = [uuid.uuid4() for _ in range(3)]
        with deterministic_ids(10):
            c = [uuid.uuid4() for _ in range(3)]
        assert a == b
        assert a != c
        assert all(u.version == 4 for u in a)
        # restored: two live mints virtually never collide
        assert uuid.uuid4() != uuid.uuid4()


class TestScenarioDsl:
    def test_arrival_times_deterministic_and_phase_bounded(self):
        import random

        sc = Scenario(
            name="x", replicas=2,
            phases=(
                LoadPhase(10.0, 2.0),
                LoadPhase(5.0, 0.0),  # silent gap
                LoadPhase(10.0, 2.0),
            ),
        )
        a = list(sc.arrival_times(random.Random(3)))
        b = list(sc.arrival_times(random.Random(3)))
        assert a == b
        assert a == sorted(a)
        assert all(0.0 <= t < 25.0 for t in a)
        # nothing arrives inside the silent phase
        assert not [t for t in a if 10.0 <= t < 15.0]

    def test_diurnal_curve_shape(self):
        phases = diurnal_phases(
            hours=24.0, trough_rps=1.0, peak_rps=9.0, steps=24
        )
        assert len(phases) == 24
        assert sum(p.duration_s for p in phases) == 24 * 3600.0
        rates = [p.rate_rps for p in phases]
        # trough at the edges, peak mid-day, symmetric-ish
        assert rates[0] < rates[11] and rates[-1] < rates[12]
        assert max(rates) <= 9.0 and min(rates) >= 1.0

    def test_scaling_preserves_per_replica_load_and_verdicts(self):
        sc = Scenario(
            name="x", replicas=40, seed=1,
            phases=(LoadPhase(10.0, 8.0),),
            tenants=(TenantSpec("t", sessions=20),),
            leases=LeaseChurn(callers=1000),
            events=(ReplicaEvent(5.0, "kill", 30),),
            checks=(Check("pop", "leases.minted", ">=", 1000.0),),
        )
        small = sc.scaled(0.1)
        assert small.replicas == 4
        assert small.phases[0].rate_rps == pytest.approx(0.8)
        assert small.events[0].replica == 3  # clamped into the fleet
        assert small.tenants[0].sessions == 2
        assert small.leases.callers == 100
        assert small.checks[0].bound == pytest.approx(100.0)

    def test_check_ops_and_missing_metric_fails(self):
        check = Check("c", "a.b", "<=", 2.0)
        assert check.evaluate(2.0) and not check.evaluate(2.5)
        assert not check.evaluate(None)  # absent metric is NOT a pass
        with pytest.raises(ValueError):
            Check("c", "a.b", "~=", 1.0)
        with pytest.raises(ValueError):
            ReplicaEvent(1.0, "explode", 0)

    def test_metric_helpers(self):
        tree = {"a": {"b": 2, "flag": True, "s": "x"}, "n": 1.5}
        assert metric_at(tree, "a.b") == 2.0
        assert metric_at(tree, "a.missing") is None
        assert metric_at(tree, "a.flag") is None  # bools are not metrics
        flat = flatten_metrics(tree)
        assert flat == {"a.b": 2.0, "n": 1.5}
        assert percentile([], 0.95) == 0.0
        assert percentile([1.0, 2.0, 10.0], 0.95) == 10.0


# --------------------------------------------------------------- runner
class TestSimRunner:
    def test_smoke_scenario_real_path(self):
        report = smoke_report()
        assert report.passed
        offered = report.metric("requests.offered")
        assert offered and offered > 50
        assert report.metric("requests.completed") == offered
        served = report.metrics["routing"]["per_replica"]
        # every replica served traffic: the router spread the fleet
        assert len(served) == 6 and all(s > 0 for s in served)
        assert report.metric("prefix.hit_rate") > 0.5  # 6 sessions repeat
        assert report.metric("tokens.tokens_per_dispatch") == 8.0
        assert report.metric("time.makespan_s") < 60.0

    def test_kill_and_heal_with_failover(self):
        sc = Scenario(
            name="heal", replicas=4, seed=8,
            phases=(LoadPhase(duration_s=90.0, rate_rps=2.0),),
            policy="least-loaded",
            service=ServiceSpec(base_s=0.8, per_token_s=0.02, slots=2),
            failover=True,
            heartbeat_every_s=5.0,
            stale_after_s=15.0,
            events=(
                ReplicaEvent(20.0, "kill", 1),
                ReplicaEvent(60.0, "resume", 1),
            ),
            per_replica_report=False,
            checks=(
                Check("all", "requests.completion_ratio", "==", 1.0),
                Check("dead_dark", "routing.delivered_while_dead", "==", 0.0),
                Check("healed", "routing.delivered_after_heal", ">=", 1.0),
            ),
        )
        report = asyncio.run(SimRunner(sc).run())
        assert report.passed, [c for c in report.checks if not c.passed]
        assert report.metric("routing.failover_arrivals") >= 1

    def test_lease_churn_folds_real_table(self):
        sc = Scenario(
            name="leases", replicas=2, seed=4,
            phases=(LoadPhase(duration_s=60.0, rate_rps=0.5),),
            leases=LeaseChurn(
                callers=200, ttl_s=10.0, beat_every_s=8.0,
                min_life_s=5.0, max_life_s=30.0,
                clean_release_ratio=0.5,
            ),
            checks=(
                Check("all", "requests.completion_ratio", "==", 1.0),
                Check("minted", "leases.minted", "==", 200.0),
                Check("lapsed", "leases.lapsed", ">=", 1.0),
            ),
        )
        report = asyncio.run(SimRunner(sc).run())
        assert report.passed, [c for c in report.checks if not c.passed]
        stats = report.metrics["leases"]
        assert stats["table_records"] > 0
        # clean releases tombstone their table record
        assert stats["released"] > 0

    def test_cap_evicts_released_corpses_before_live_leases(self):
        """Review-caught regression guard (ISSUE 11): the amortized
        prune's O(1) LRU backstop must consume released tombstones
        before it can ever touch a LIVE lease — an evicted live lease
        reads never-seen = alive forever and permanently un-reaps its
        runs.  Released entries therefore park at the LRU front."""
        from calfkit_tpu import leases
        from calfkit_tpu.sim import virtual_clock
        from calfkit_tpu.sim.runner import fresh_lease_store

        with virtual_clock(), fresh_lease_store():
            cap = leases._BEAT_CAP
            for i in range(cap):
                leases.note_beat(f"live-{i:05d}", 30.0)
            for i in range(0, cap, 2):
                leases.release_lease(f"live-{i:05d}")
            # churn well past one amortization window of fresh inserts:
            # every eviction must land on a released corpse
            for i in range(cap // 2):
                leases.note_beat(f"fresh-{i:05d}", 30.0)
            store = leases.active_leases()
            assert len(store) <= cap
            evicted_live = [
                f"live-{i:05d}"
                for i in range(1, cap, 2)
                if f"live-{i:05d}" not in store
            ]
            assert not evicted_live, (
                f"{len(evicted_live)} live leases evicted while released "
                "corpses survived"
            )
            assert all(f"fresh-{i:05d}" in store for i in range(cap // 2))

    def test_lease_store_isolated_between_runs(self):
        from calfkit_tpu import leases

        before = dict(leases.active_leases())
        sc = Scenario(
            name="leases", replicas=2, seed=4,
            phases=(LoadPhase(duration_s=20.0, rate_rps=0.5),),
            leases=LeaseChurn(callers=50, min_life_s=5.0, max_life_s=10.0),
        )
        asyncio.run(SimRunner(sc).run())
        assert dict(leases.active_leases()) == before


class TestFailoverUncharge:
    """The simulator-caught bug (ISSUE 11): abandoning a dead placement
    must clear the router's least-request entry for the corpse — no
    terminal will ever fire the done-callback that normally clears it,
    and a healed replica carrying phantom in-flight load is starved by
    least-loaded routing for the whole TTL."""

    def test_failover_uncharges_the_corpse(self):
        from calfkit_tpu.client import Client
        from calfkit_tpu.fleet import FleetRouter
        from calfkit_tpu.fleet.failover import FailoverPolicy
        from calfkit_tpu.mesh import InMemoryMesh
        from calfkit_tpu.sim import (
            FleetTopology,
            SimEngineModel,
            settle,
            virtual_clock,
        )

        async def scenario() -> None:
            with deterministic_ids(3), virtual_clock() as clock:
                mesh = InMemoryMesh()
                service = ServiceSpec(base_s=50.0, per_token_s=0.0, slots=2)
                models = [
                    SimEngineModel(clock, index=i, service=service)
                    for i in range(2)
                ]
                topo = FleetTopology(
                    mesh, models, heartbeat_interval=1e6,
                    stale_multiplier=1.0,
                )
                async with topo:
                    router = FleetRouter(
                        mesh, "least-loaded", stale_after=15.0
                    )
                    client = Client.connect(mesh, router=router)
                    await router.start()
                    await topo.beat_all()
                    await settle(
                        lambda: len(router.registry.eligible("svc")) == 2,
                        interval=0, ticks=5000,
                    )
                    task = asyncio.ensure_future(
                        client.agent("svc").execute(
                            "corpse-uncharge probe",
                            timeout=3600,
                            failover=FailoverPolicy(
                                probe_interval=0.0, max_failovers=2
                            ),
                        )
                    )
                    # the tie-broken least-loaded pick: lowest replica key
                    victim = topo.index_of_lowest_key()
                    survivor = 1 - victim
                    await settle(
                        lambda: models[victim].active == 1,
                        interval=0, ticks=5000,
                    )
                    victim_key = topo.replica_key(victim)
                    assert router._outstanding(victim_key) == 1
                    topo.kill(victim)
                    clock.advance(16.0)  # stale, but its 50s service isn't due
                    # re-stamp the survivor (the corpse's beat is dropped
                    # by its dead transport — its stamp stays frozen)
                    await topo.beat_all()
                    await settle(
                        lambda: models[survivor].active == 1,
                        interval=0, ticks=20_000,
                        message="failover re-dispatch never landed",
                    )
                    # THE law: the corpse is uncharged the moment the
                    # supervisor abandons the placement — not at TTL
                    assert router._outstanding(victim_key) == 0
                    # walk time to the survivor's completion in sub-stale
                    # steps with beats between (one long advance would
                    # stale the survivor's advert and the supervisor
                    # would — correctly — declare IT dead too)
                    for _ in range(6):
                        clock.advance(10.0)
                        await topo.beat_all()
                        for _ in range(40):
                            await asyncio.sleep(0)
                    await settle(lambda: task.done(), interval=0, ticks=20_000)
                    result = await task
                    assert result.output is not None
                    assert router._outstanding(topo.replica_key(survivor)) == 0
                    await client.close()
                await mesh.stop()

        asyncio.run(scenario())


# ---------------------------------------------------------- determinism
class TestDeterminism:
    def test_same_seed_byte_identical_single_scenario(self):
        """Tier-1's fast determinism oracle: one scenario, twice."""
        a = smoke_report()
        b = asyncio.run(SimRunner(SMOKE).run())
        assert json.dumps(a.to_dict(), sort_keys=True) == json.dumps(
            b.to_dict(), sort_keys=True
        )

    def test_different_seed_differs_but_verdicts_hold(self):
        from dataclasses import replace

        a = smoke_report()
        b = asyncio.run(SimRunner(replace(SMOKE, seed=6)).run())
        assert a.metrics != b.metrics
        assert a.passed and b.passed

    @pytest.mark.slow
    def test_pinned_suite_byte_identical_and_seed_robust(self):
        """The ISSUE-11 acceptance law, full shape (scaled for CI): the
        whole pinned suite twice with the same seeds → byte-identical
        SIM.json modulo the capture block; every scenario re-seeded →
        verdicts still pass."""
        from dataclasses import replace

        async def run_suite(bump: int = 0) -> SimReport:
            report = SimReport(suite=SUITE_NAME)
            for scenario in scaled_suite(0.15):
                if bump:
                    scenario = replace(scenario, seed=scenario.seed + bump)
                report.scenarios.append(
                    await SimRunner(scenario).run()
                )
            return report

        first = asyncio.run(run_suite())
        second = asyncio.run(run_suite())
        doc_a = strip_capture(first.to_dict(capture={"captured_at": "A"}))
        doc_b = strip_capture(second.to_dict(capture={"captured_at": "B"}))
        assert json.dumps(doc_a, sort_keys=True) == json.dumps(
            doc_b, sort_keys=True
        )
        reseeded = asyncio.run(run_suite(bump=1000))
        assert reseeded.passed, [
            (s.name, [c for c in s.checks if not c.passed])
            for s in reseeded.scenarios
            if not s.passed
        ]


# -------------------------------------------------------------- the gate
class TestPerfGate:
    def test_baseline_round_trip_passes(self):
        gate = _load_perf_gate()
        report = SimReport(suite=SUITE_NAME)
        report.scenarios.append(smoke_report())
        baseline = gate.baseline_from(report)
        assert gate.compare_to_baseline(report, baseline) == []

    def test_tolerance_band_and_exact_metrics(self):
        gate = _load_perf_gate()
        report = SimReport(suite=SUITE_NAME)
        report.scenarios.append(smoke_report())
        baseline = gate.baseline_from(report)
        entry = baseline["scenarios"]["smoke"]["requests.completed"]
        # requests.completed is an EXACT metric: zero tolerance
        assert entry["rel_tol"] == 0.0 and entry["abs_tol"] == 0.0
        entry["value"] += 1
        problems = gate.compare_to_baseline(report, baseline)
        assert problems and "requests.completed" in problems[0]

    def test_missing_gated_metric_is_a_regression(self):
        gate = _load_perf_gate()
        report = SimReport(suite=SUITE_NAME)
        report.scenarios.append(smoke_report())
        baseline = gate.baseline_from(report)
        baseline["scenarios"]["smoke"].pop("requests.completed")
        assert gate.compare_to_baseline(report, baseline)
        assert gate.compare_to_baseline(
            report, {"scenarios": {}}
        )  # absent scenario = regression too

    def test_seeded_regression_trips_the_gate(self):
        """The acceptance demonstration: a deliberately degraded routing
        policy (worst-loaded placement) against a healthy baseline must
        FAIL the gate — on the skew verdict, the baseline band, or
        both."""
        gate = _load_perf_gate()
        scenario = Scenario(
            name="smoke",  # same name: compares against smoke's baseline
            replicas=SMOKE.replicas,
            seed=SMOKE.seed,
            phases=SMOKE.phases,
            service=SMOKE.service,
            tenants=SMOKE.tenants,
            checks=SMOKE.checks
            + (Check("skew", "routing.skew_p95_over_mean", "<=", 1.7),),
            gated=SMOKE.gated + ("routing.skew_p95_over_mean",),
        )
        healthy = SimReport(suite=SUITE_NAME)
        healthy.scenarios.append(asyncio.run(SimRunner(scenario).run()))
        assert healthy.passed
        baseline = gate.baseline_from(healthy)

        degraded = SimReport(suite=SUITE_NAME)
        degraded.scenarios.append(
            asyncio.run(
                SimRunner(scenario, policy=gate._WorstLoaded()).run()
            )
        )
        problems = gate.compare_to_baseline(degraded, baseline)
        assert problems, "a worst-loaded policy must trip the gate"
        # and the degradation is visible in the metric itself
        assert degraded.scenarios[0].metric(
            "routing.skew_p95_over_mean"
        ) > healthy.scenarios[0].metric("routing.skew_p95_over_mean")

    def test_committed_sim_artifact_matches_suite(self):
        """SIM.json at the repo root is the pinned suite's output: every
        pinned scenario present, every verdict green, capture block
        carries provenance."""
        with open(os.path.join(REPO, "SIM.json")) as f:
            document = json.load(f)
        assert document["suite"] == SUITE_NAME
        assert document["passed"] is True
        names = {s["name"] for s in document["scenarios"]}
        assert names == {s.name for s in PINNED_SUITE}
        for scenario in document["scenarios"]:
            assert scenario["passed"], scenario["name"]
        assert document["capture"].get("captured_at")

    def test_committed_baseline_covers_gated_metrics(self):
        with open(os.path.join(REPO, "SIM_BASELINE.json")) as f:
            baseline = json.load(f)
        for scenario in PINNED_SUITE:
            entry = baseline["scenarios"][scenario.name]
            assert set(entry) == set(scenario.gated)


# ----------------------------------------------------------------- shim
class TestChaosShim:
    def test_legacy_imports_still_resolve(self):
        import tests._chaos as shim
        from calfkit_tpu import sim

        for name in (
            "VirtualClock", "virtual_clock", "ChaosScript", "BrokerChaos",
            "settle", "assert_engine_drained", "FleetTopology",
            "ReplicaTransport", "ServingStubModel", "StreamingStubModel",
            "BijectiveTokenizer",
        ):
            assert getattr(shim, name) is getattr(sim, name), name
        assert "DEPRECATED" in (shim.__doc__ or "")


# ------------------------------------------------------------------ CLI
class TestCkSim:
    def test_render_sim_table(self):
        from calfkit_tpu.cli.sim import render_sim_table

        report = SimReport(suite=SUITE_NAME)
        report.scenarios.append(smoke_report())
        doc = report.to_dict(capture={"captured_at": "T", "wall_s": 1.0})
        text = render_sim_table(doc)
        assert "SCENARIO" in text and "smoke" in text
        assert "pass" in text
        assert "not a gated metric" in text  # wall time is provenance only

        # failed checks always expand
        doc["scenarios"][0]["checks"][0]["passed"] = False
        doc["scenarios"][0]["passed"] = False
        text = render_sim_table(doc)
        assert "FAIL" in text and "all_complete" in text

    def test_ck_registers_sim(self):
        from calfkit_tpu.cli.main import main as ck

        assert "sim" in ck.commands


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
