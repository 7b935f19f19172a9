"""TPC-H Q9 (product type profit measure): the profit on every part
whose name holds COLOR, by the supplier's nation and the order's year."""

import numpy as np

from chipbench.references._common import (
    add_sums, blocks, col, dec, dict_values, group_sums,
)


def matches(name: str, color: str) -> bool:
    """p_name like '%COLOR%'."""
    return color in name


def by_key(keys: np.ndarray):
    """(keys sorted, the row each came from)."""
    order = np.argsort(keys, kind="stable")
    return keys[order], order


def lookup(sorted_keys: np.ndarray, wanted: np.ndarray):
    """(position of each wanted key in `sorted_keys`, whether it is there)."""
    pos = np.searchsorted(sorted_keys, wanted)
    pos[pos == len(sorted_keys)] = 0
    found = sorted_keys[pos] == wanted if len(sorted_keys) else np.zeros(len(wanted), bool)
    return pos, found


def year_of(days_since_epoch: np.ndarray) -> np.ndarray:
    return days_since_epoch.astype("datetime64[D]").astype("datetime64[Y]").astype(np.int64) + 1970


def reference(tables, params, sums=group_sums, match=matches, year_from="o_orderdate",
              with_supply_cost=True):
    names = dict_values(tables, "part", "p_name")
    coloured = np.asarray([match(v, params["color"]) for v in names], dtype=bool)
    p_key = np.sort(col(tables, "part", "p_partkey")[coloured[col(tables, "part", "p_name")]])
    # partsupp by the pair: part keys and supplier keys are positive and
    # under 2^31, so the pair is one int64
    pair = lambda part, supp: (part.astype(np.int64) << 32) | supp.astype(np.int64)  # noqa: E731
    ps_pair, ps_row = by_key(pair(col(tables, "partsupp", "ps_partkey"),
                                  col(tables, "partsupp", "ps_suppkey")))
    ps_cost = col(tables, "partsupp", "ps_supplycost")[ps_row]
    s_key, s_row = by_key(col(tables, "supplier", "s_suppkey"))
    s_nation = col(tables, "supplier", "s_nationkey")[s_row]
    n_key, n_row = by_key(col(tables, "nation", "n_nationkey"))
    o_key, o_row = by_key(col(tables, "orders", "o_orderkey"))
    o_date = col(tables, "orders", "o_orderdate")[o_row]
    first_year = int(year_of(o_date).min()) if len(o_date) else 1970
    if year_from != "o_orderdate":
        first_year -= 1   # a lineitem's own dates reach into the years around
    n_years = 16
    total = None
    line = {c: col(tables, "lineitem", c) for c in tables["lineitem"]}
    for rows in blocks(len(line["l_partkey"])):
        part, supp = line["l_partkey"][rows], line["l_suppkey"][rows]
        keep = lookup(p_key, part)[1]
        ps_pos, found = lookup(ps_pair, pair(part, supp))
        keep &= found
        s_pos, found = lookup(s_key, supp)
        keep &= found
        o_pos, found = lookup(o_key, line["l_orderkey"][rows])
        keep &= found
        n_pos, found = lookup(n_key, s_nation[s_pos])
        keep &= found
        date = o_date[o_pos] if year_from == "o_orderdate" else line[year_from][rows]
        group = n_pos[keep] * n_years + (year_of(date[keep]) - first_year)
        # amount in ten-thousandths: price x (100 - discount) less
        # supply cost x quantity, each of them hundredths
        revenue = line["l_extendedprice"][rows][keep] * (100 - line["l_discount"][rows][keep])
        cost = ps_cost[ps_pos[keep]] * line["l_quantity"][rows][keep]
        if not with_supply_cost:
            cost = np.zeros_like(cost)
        total = add_sums(total, sums(group, len(n_key) * n_years, revenue, cost))
    revenue, cost, count = total
    n_names = dict_values(tables, "nation", "n_name")
    n_name = col(tables, "nation", "n_name")[n_row]
    out = [
        [n_names[int(n_name[g // n_years])], first_year + int(g % n_years),
         dec(int(revenue[g]) - int(cost[g]), 4)]
        for g in np.nonzero(count)[0]
    ]
    # order by nation, o_year desc
    return sorted(out, key=lambda r: (r[0], -r[1]))
