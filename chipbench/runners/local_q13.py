"""Runner kind `local_q13`: the `local` runner (one LocalQueryRunner over
the memory connector), for a program that plans Q13 the way the
deployment `tpch-sf10-q13-1chip` is sized for.

Q13 joins every customer to its orders under a LEFT OUTER JOIN: 1.5 M
customers, each key once, against 15 M orders with ten or more a key.
Planned with the customers as the lookup, every batch of orders finds
one candidate a row and the customers no order matched come out once at
the end. A program that can only build the null-supplying side builds
all 15 M orders (2^24 slots of three columns), sends both batches of
customers through the general expansion into 2^23 slots of pairs each,
and evaluates the comment's NOT LIKE on the pairs (chipbench/Q13.md,
step 0). EXPLAIN costs milliseconds and runs nothing, so such a program
is told so before its first statement, and the run ends with exit code
1 inside a minute instead of holding a chip."""

import os
import re

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
STATEMENT = "q13"
FACT_TABLE = "orders"


def fact_table_built_under_a_left_join(plan_text: str) -> bool:
    """Whether EXPLAIN's plan has a `Join left` that builds its second
    child (no ` build=left` on its line) where that child is a scan of
    the fact table under filters and projections only."""
    lines = plan_text.splitlines()
    indent = [len(line) - len(line.lstrip()) for line in lines]
    for at, line in enumerate(lines):
        if not re.match(r"\s*Join left ", line) or " build=left" in line:
            continue
        children = []
        for i in range(at + 1, len(lines)):
            if indent[i] <= indent[at]:
                break
            if indent[i] == indent[at] + 2:
                children.append(i)
        if len(children) < 2:
            continue
        i = children[1]
        while re.match(r"\s*(Filter|Project) ", lines[i]):
            i += 1
        if re.match(rf"\s*Scan \S+\.{FACT_TABLE} ", lines[i]):
            return True
    return False


def build(config: dict, tables):
    local = traffic.load_module(os.path.join(HERE, "local.py"))
    runner = local.build(config, tables)
    params = traffic.load_json(os.path.join(
        os.path.dirname(HERE), "statements", f"{STATEMENT}.json"))["validation"]
    sql = traffic.instantiate(traffic.load_statement(STATEMENT), params).sql
    plan_text = runner.execute("explain " + sql).rows[0][0]
    if fact_table_built_under_a_left_join(plan_text):
        raise SystemExit(
            f"chipbench: this program builds all of {FACT_TABLE} under "
            f"{STATEMENT}'s left join; configuration {config['name']} "
            "needs the side the join preserves built (chipbench/Q13.md): not run"
        )
    return runner
