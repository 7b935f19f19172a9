"""Runner kind `local_q21`: the `local` runner (one LocalQueryRunner over
the memory connector), for a program that plans Q21 the way the
deployment `tpch-sf10-q21-1chip` is sized for.

Q21's EXISTS and NOT EXISTS each ask `lineitem` about the lines of one
nation's late suppliers: about 0.8 M rows of 60 M survive the three
inner joins, and a line of the subquery's side whose order none of them
has decides nothing. Planned with the small side as the lookup, its keys
filter the other two scans of the fact table before they probe. A
program that builds the subquery's side builds a whole scan of
`lineitem` twice a statement, 59,992,734 rows and about 38 M, and reads
all three scans from ONE device copy of the table, so that the
harness's residency check (rows scanned x the narrowest row against the
bytes in use) can only fail it after a cold warm-up of many minutes
(chipbench/Q21.md, step 0). EXPLAIN costs milliseconds and runs nothing,
so such a program is told so before its first statement, and the run
ends with exit code 1 inside a minute instead of holding a chip."""

import os
import re

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
STATEMENT = "q21"
FACT_TABLE = "lineitem"


def fact_table_built_under_a_semi_join(plan_text: str) -> bool:
    """Whether EXPLAIN's plan has a `Join semi` or `Join anti` that
    builds its second child (no ` build=left` on its line) where that
    child is a scan of the fact table under filters and projections
    only."""
    lines = plan_text.splitlines()
    indent = [len(line) - len(line.lstrip()) for line in lines]
    for at, line in enumerate(lines):
        if not re.match(r"\s*Join (semi|anti) ", line) or " build=left" in line:
            continue
        children = [i for i in range(at + 1, len(lines))
                    if indent[i] == indent[at] + 2]
        for i in range(at + 1, len(lines)):
            if indent[i] <= indent[at]:
                children = [c for c in children if c < i]
                break
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
    if fact_table_built_under_a_semi_join(plan_text):
        raise SystemExit(
            f"chipbench: this program builds a whole scan of {FACT_TABLE} under "
            f"{STATEMENT}'s semi- or anti-join; configuration {config['name']} "
            "needs the side the join preserves built (chipbench/Q21.md): not run"
        )
    return runner
