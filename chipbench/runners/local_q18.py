"""Runner kind `local_q18`: the `local` runner (one LocalQueryRunner over
the memory connector), for a program that plans Q18 the way the
deployment `tpch-sf10-q18-1chip` is sized for.

Q18's `o_orderkey in (select ... having ...)` filters `orders`; planned
as a semi-join on the `orders` scan, below both inner joins, the joins
see the few hundred surviving rows. A program that plans the semi-join
above them probes all 60 M lineitem rows into the whole `orders x
customer` build and carries every one out: at SF10 its first statement
compiles 59 programs for over 21 minutes, the harness's client gives up
at its 1,100 s, and the process, which waits for the statement's thread,
had not ended at 1,508 s (PERF.md section 6, PR 33, step 0). EXPLAIN
costs milliseconds and runs nothing, so such a program is told so
before its first statement, and the run ends with exit code 1 inside a
minute instead of holding a chip for half an hour."""

import os

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
STATEMENT = "q18"


def semi_join_above_a_join(plan_text: str) -> bool:
    """Whether EXPLAIN's plan has a `Join semi` nearer the root than a
    `Join inner`. (A plan with no semi-join line has found another way
    to ask the IN set, and passes.)"""
    depth = {"semi": [], "inner": []}
    for line in plan_text.splitlines():
        for kind, found in depth.items():
            if line.lstrip().startswith(f"Join {kind}"):
                found.append(len(line) - len(line.lstrip()))
    return bool(depth["semi"] and depth["inner"]
                and min(depth["semi"]) < max(depth["inner"]))


def build(config: dict, tables):
    local = traffic.load_module(os.path.join(HERE, "local.py"))
    runner = local.build(config, tables)
    params = traffic.load_json(os.path.join(
        os.path.dirname(HERE), "statements", f"{STATEMENT}.json"))["validation"]
    sql = traffic.instantiate(traffic.load_statement(STATEMENT), params).sql
    plan_text = runner.execute("explain " + sql).rows[0][0]
    if semi_join_above_a_join(plan_text):
        raise SystemExit(
            f"chipbench: this program plans {STATEMENT}'s semi-join above its "
            f"inner joins; configuration {config['name']} needs it on the "
            "scan that holds its key (chipbench/Q18.md): not run"
        )
    return runner
