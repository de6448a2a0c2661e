"""meshlint configuration: what to scan and where each rule applies.

``default_config`` is the calfkit-tpu instance; tests build their own
``Config`` around fixture trees.  Everything here is data, not code —
the rules in :mod:`meshlint.rules` read these scopes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path


@dataclass
class RequiredRoots:
    """Loud-miss floor: at least ``min_count`` functions under
    ``module_prefix`` must carry ``marker``.  This is the rename-proof
    replacement for the old hand-curated name lists: a wholesale
    decorator removal (or a module rename that drops the tree from the
    scan) fails the lint loudly instead of silently linting nothing."""
    module_prefix: str
    marker: str
    min_count: int
    hint: str = ""


@dataclass
class Config:
    root: Path
    # directories/files (relative to root) to parse into the call graph
    scan: "list[str]" = field(default_factory=lambda: ["calfkit_tpu"])
    # module prefix owning the whole-package async rules (event-loop
    # stall + await atomicity); "" disables both
    package_prefix: str = "calfkit_tpu"
    # module prefixes under the unbounded-queue rule (ISSUE 5 scope)
    queue_scope: "list[str]" = field(default_factory=list)
    # module prefix under the direct wall-clock ban (ISSUE 11); "" off
    sim_scope: str = ""
    # module whose `._journal.append(...)` sites must not format (ISSUE 4)
    journal_module: str = ""
    # (module, class, method) whose body is held to the O(1) journal
    # promise: no formatting, no logging, no time.time (ISSUE 4)
    flightrec_append: "tuple[str, str, str] | None" = None
    required_roots: "list[RequiredRoots]" = field(default_factory=list)


def default_config(root: "Path | str") -> Config:
    root = Path(root)
    return Config(
        root=root,
        scan=["calfkit_tpu", "scripts/perf_gate.py"],
        package_prefix="calfkit_tpu",
        queue_scope=[
            "calfkit_tpu.inference.engine",
            "calfkit_tpu.mesh.dispatch",
            "calfkit_tpu.fleet",
            "calfkit_tpu.sim",
            "calfkit_tpu.leases",
        ],
        sim_scope="calfkit_tpu.sim",
        journal_module="calfkit_tpu.inference.engine",
        flightrec_append=(
            "calfkit_tpu.observability.flightrec", "FlightRecorder", "append",
        ),
        required_roots=[
            RequiredRoots(
                "calfkit_tpu.inference.engine", "hotpath", 8,
                "the decode dispatch loop (ISSUE 2/3/6) and the "
                "priority-shed selection / class-weighted reap ordering "
                "(ISSUE 20) must stay rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.fleet", "hotpath", 8,
                "the per-dispatch selection path (ISSUE 7/9) must stay "
                "rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.leases", "hotpath", 5,
                "the orphan-reaper sweep reads (ISSUE 10) and the "
                "shed-order beat-age read (ISSUE 20) must stay rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.qos", "hotpath", 2,
                "the per-delivery admission token-bucket check and the "
                "class-rank ordering key (ISSUE 20) must stay rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.observability.flightrec", "hotpath", 1,
                "FlightRecorder.append's O(1) promise (ISSUE 4) must stay "
                "rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.observability.runledger", "hotpath", 5,
                "the run ledger's O(1) append promise (ISSUE 17: begin/"
                "attempt/outcome/tokens/finish) must stay rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.observability.runledger", "no_wallclock", 2,
                "the SLO rollup fold (ISSUE 17) is gated by the sim — it "
                "must never read host time",
            ),
            RequiredRoots(
                "calfkit_tpu.observability.capacity", "hotpath", 7,
                "the page ledger's O(1) mutation promise (ISSUE 19: "
                "alloc/free/transfer/acquire/release/evicted + sampler "
                "append) must stay rooted",
            ),
            RequiredRoots(
                "calfkit_tpu.observability.capacity", "no_wallclock", 2,
                "the capacity rollup math (ISSUE 19: breakdown, the "
                "analytic HBM model) is gated by the sim — it must never "
                "read host time",
            ),
            RequiredRoots(
                "perf_gate", "no_wallclock", 1,
                "the gate's metric compare must never read host time "
                "(ISSUE 11)",
            ),
        ],
    )
