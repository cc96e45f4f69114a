"""Cost model: cpu / memory / network terms over row estimates.

Analogue of the reference CBO's cost side — cost/CostCalculatorUsingExchanges
.java:66 (exchange network terms), cost/LocalCostEstimate (cpu/memory per
operator) — narrowed to the decisions this engine makes from cost:

  * join ORDER (optimizer.reorder_joins asks for the cheapest next join)
  * join DISTRIBUTION (add_exchanges compares broadcast vs repartition
    network+memory, the DetermineJoinDistributionType analogue)

Row estimates come from optimizer.estimate_rows (connector row counts +
fixed selectivities — the StatsCalculator stand-in). What prices a join
STEP of the greedy order is `join_output_rows` below (optimizer.join_rows
looks its statistics up): the probe's rows x the build's rows over each
clause's larger distinct count (a foreign key's distinct count is its
referenced table's row count, the connector says), with the clauses that
cover a unique column set of the build counted as ONE clause whose distinct
count is the build table's row count. So a join on a 25-value key fans out,
a join on a primary key — single or composite — cannot, and the step costs
`join_step_cost(stream, build, max(stream, output))`: a probe masks the rows
it drops and the page keeps its slots, so only a fan-out widens the stream.
(estimate_rows' JoinNode arm, which sizes a subquery's joins and the probe
of add_exchanges' broadcast choice, keeps the plain per-clause form: PERF.md
§7.) Costs are unit-weight
abstract numbers: 1 cpu = one row touched, 1 memory = one build row held
device-resident, 1 network = one row crossing the exchange. TPU framing:
memory is HBM (the scarcest resource — build sides must fit), network is
ICI hops (cheap inside a slice but not free), cpu is VPU/MXU row work.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class PlanCost:
    cpu: float = 0.0
    memory: float = 0.0
    network: float = 0.0

    def plus(self, other: "PlanCost") -> "PlanCost":
        return PlanCost(self.cpu + other.cpu,
                        self.memory + other.memory,
                        self.network + other.network)

    def total(self, cpu_w: float = 1.0, mem_w: float = 2.0,
              net_w: float = 2.0) -> float:
        """Scalarization for comparisons. Memory and network weigh heavier
        than cpu: HBM residency and ICI traffic are the scaling walls."""
        return cpu_w * self.cpu + mem_w * self.memory + net_w * self.network

    def __repr__(self):
        return (f"PlanCost(cpu={self.cpu:.3g}, mem={self.memory:.3g}, "
                f"net={self.network:.3g})")


ZERO = PlanCost()


def join_output_rows(probe_rows: float, build_rows: float,
                     clause_ndvs: Sequence[Optional[float]],
                     unique_key_rows: Optional[float] = None) -> float:
    """Rows an inner equi-join emits (JoinStatsRule.java): |probe x build|
    over the larger distinct count of each clause, the clauses taken as
    independent.

    `clause_ndvs` has one entry a clause, max(NDV of the probe's key, NDV of
    the build's), None where neither side knows it. `unique_key_rows` is set
    where some clauses cover a unique column set of the build, and those
    clauses are then NOT among `clause_ndvs`: it is the distinct count of the
    SET, the build table's row count before its filters. Independence would
    multiply the set's columns (partsupp: 200,000 x 10,000 for 800,000 pairs)
    and put a join that cannot fan out far under its probe's rows; as one
    clause it keeps of the probe the share the build's filters kept, and
    never more than the probe. No clause at all is a cross product; clauses
    whose distinct counts nobody knows fall back to the larger input."""
    if unique_key_rows is None and not clause_ndvs:
        return probe_rows * build_rows
    out = probe_rows * build_rows
    known = False
    if unique_key_rows is not None:
        out /= max(unique_key_rows, build_rows, 1.0)
        known = True
    for ndv in clause_ndvs:
        if ndv:
            out /= ndv
            known = True
    return max(1.0, out) if known else max(probe_rows, build_rows)


def join_step_cost(probe_rows: float, build_rows: float,
                   output_rows: float) -> PlanCost:
    """One hash-join step: build the table (cpu+memory), stream the probe,
    emit the output (LocalCostEstimate for HashBuilder+LookupJoin)."""
    return PlanCost(cpu=probe_rows + build_rows + output_rows,
                    memory=build_rows,
                    network=0.0)


def broadcast_cost(build_rows: float, n_workers: int) -> PlanCost:
    """Replicate the build side to every worker: network scales with W, and
    every worker holds a full copy in HBM."""
    return PlanCost(cpu=0.0,
                    memory=build_rows * n_workers,
                    network=build_rows * max(n_workers - 1, 1))


def repartition_cost(probe_rows: float, build_rows: float) -> PlanCost:
    """Hash-repartition BOTH sides: every row crosses the mesh once; each
    worker holds build/W rows (counted as build total across the mesh)."""
    return PlanCost(cpu=0.0,
                    memory=build_rows,
                    network=probe_rows + build_rows)


def cheaper_to_broadcast(probe_rows: float, build_rows: float,
                         n_workers: int,
                         broadcast_memory_limit_rows: float) -> bool:
    """DetermineJoinDistributionType.java's AUTOMATIC decision by cost:
    replicate small builds (saves repartitioning the big probe) unless the
    replicated table would blow the per-worker HBM budget."""
    if build_rows > broadcast_memory_limit_rows:
        return False
    bc = broadcast_cost(build_rows, n_workers)
    rp = repartition_cost(probe_rows, build_rows)
    return bc.total() < rp.total()
