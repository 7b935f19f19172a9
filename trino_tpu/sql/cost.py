"""Plan cost model.

Analogue of main/cost/ (PlanCostEstimate, CostCalculatorUsingExchanges,
TaskCountEstimator — SURVEY.md §2.2): a three-component cost
(cpu, memory, network) derived from the StatsCalculator's row
estimates, consumed by the join-reordering optimizer and by EXPLAIN.

The constants encode the TPU engine's actual cost shape rather than the
reference's JVM one: a hash-join build is a device sort (n log n-ish but
modeled linear with a higher constant), the probe is a sorted-run merge
(linear), and a repartition exchange moves every byte through
host<->device once under the page data plane — so network weight is
high, which biases the reorderer toward smaller intermediate results,
exactly the property the mesh data plane wants too.
"""

from __future__ import annotations

import dataclasses

from trino_tpu.sql import plan as P
from trino_tpu.sql.stats import StatsCalculator, key_is_unique

# relative per-row weights
_CPU_SCAN = 1.0
_CPU_FILTER = 0.5
_CPU_PROJECT = 0.5
_CPU_PROBE = 2.0
_CPU_BUILD = 4.0       # sort-based lookup build: costlier than probe
# every output row of a join gathers the build side's columns; the
# probe side's pass through untouched where the build side's key is
# unique (no probe row matches twice) and are gathered too where it is
# not. A random gather of one element costs what sorting several rows
# does (16.5 ms against 2 ms a million on a v5e, ops/join.py), so a wide
# side under a gather is dear whatever its row count
_CPU_PAIR_COLUMN = 3.0
_CPU_AGG = 3.0
_CPU_SORT = 6.0
_NET_PER_ROW = 8.0     # exchange: dominant on the host data plane
_MEM_PER_ROW = 1.0


@dataclasses.dataclass(frozen=True)
class PlanCost:
    """PlanCostEstimate analogue; `total` is the scalar the optimizer
    ranks by (CostComparator with uniform weights)."""

    cpu: float
    memory: float
    network: float

    @property
    def total(self) -> float:
        return self.cpu + self.memory + self.network

    def plus(self, other: "PlanCost") -> "PlanCost":
        return PlanCost(
            self.cpu + other.cpu,
            self.memory + other.memory,
            self.network + other.network,
        )


ZERO_COST = PlanCost(0.0, 0.0, 0.0)


class CostCalculator:
    """Bottom-up cumulative cost (CostCalculatorWithEstimatedExchanges:
    local cost of each node + its children's, with exchange cost imputed
    where the fragmenter will cut)."""

    def __init__(self, stats: StatsCalculator):
        self._stats = stats
        self._memo = {}

    def cost(self, node: P.PlanNode) -> PlanCost:
        key = id(node)
        hit = self._memo.get(key)
        if hit is not None and hit[0] is node:
            return hit[1]
        out = self._local(node)
        for c in node.children():
            out = out.plus(self.cost(c))
        self._memo[key] = (node, out)
        return out

    def _rows(self, node: P.PlanNode) -> float:
        return self._stats.stats(node).row_count

    def _local(self, node: P.PlanNode) -> PlanCost:
        if isinstance(node, P.ScanNode):
            return PlanCost(self._rows(node) * _CPU_SCAN, 0.0, 0.0)
        if isinstance(node, P.FilterNode):
            return PlanCost(self._rows(node.child) * _CPU_FILTER, 0.0, 0.0)
        if isinstance(node, P.ProjectNode):
            return PlanCost(self._rows(node.child) * _CPU_PROJECT, 0.0, 0.0)
        if isinstance(node, P.JoinNode):
            probe = self._rows(node.left)
            build = self._rows(node.right)
            out = self._rows(node)
            # imputed exchange cost: the fragmenter will repartition (or
            # broadcast) both join inputs, so every input row crosses
            # the host data plane once — this is what actually biases
            # the reorderer toward small intermediates
            # (CostCalculatorWithEstimatedExchanges discipline)
            columns = len(node.right.fields)
            # (a key of several columns has no unique flag to carry: the
            # estimate that no probe row finds two rows stands in for it)
            if not (
                key_is_unique(self._stats.stats(node.right), node.right_keys)
                or len(node.right_keys) > 1 and out <= probe
            ):
                columns += len(node.left.fields)
            gathered = out * columns * _CPU_PAIR_COLUMN
            return PlanCost(
                probe * _CPU_PROBE + build * _CPU_BUILD + out + gathered,
                build * _MEM_PER_ROW,
                (probe + build) * _NET_PER_ROW,
            )
        if isinstance(node, P.AggregateNode):
            rows = self._rows(node.child)
            groups = self._rows(node)
            return PlanCost(rows * _CPU_AGG, groups * _MEM_PER_ROW, 0.0)
        if isinstance(node, (P.SortNode, P.TopNNode)):
            rows = self._rows(node.child)
            mem = rows if isinstance(node, P.SortNode) else float(
                getattr(node, "count", 0)
            )
            return PlanCost(rows * _CPU_SORT, mem * _MEM_PER_ROW, 0.0)
        if isinstance(node, P.WindowNode):
            rows = self._rows(node.child)
            return PlanCost(rows * _CPU_SORT, rows * _MEM_PER_ROW, 0.0)
        if isinstance(node, P.ExchangeNode):
            rows = self._rows(node.child)
            return PlanCost(0.0, 0.0, rows * _NET_PER_ROW)
        return ZERO_COST
