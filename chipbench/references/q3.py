"""TPC-H Q3 (shipping priority): SEGMENT's unshipped orders before
DATE, the ten of highest revenue."""

import numpy as np

from chipbench.references._common import col, days, dec, dict_values, group_sums


def reference(tables, params, sums=group_sums):
    cutoff = days(params["date"])
    seg = dict_values(tables, "customer", "c_mktsegment").index(params["segment"])
    in_segment = col(tables, "customer", "c_custkey")[
        col(tables, "customer", "c_mktsegment") == seg
    ]
    o_keep = (
        (col(tables, "orders", "o_orderdate") < cutoff)
        & np.isin(col(tables, "orders", "o_custkey"), in_segment)
    )
    o_key = col(tables, "orders", "o_orderkey")[o_keep]
    o_date = col(tables, "orders", "o_orderdate")[o_keep]
    o_prio = col(tables, "orders", "o_shippriority")[o_keep]
    order = np.argsort(o_key, kind="stable")
    o_key, o_date, o_prio = o_key[order], o_date[order], o_prio[order]
    l_key = col(tables, "lineitem", "l_orderkey")
    l_keep = col(tables, "lineitem", "l_shipdate") > cutoff
    pos = np.searchsorted(o_key, l_key)
    pos[pos == len(o_key)] = 0
    l_keep &= o_key[pos] == l_key if len(o_key) else False
    revenue = (
        col(tables, "lineitem", "l_extendedprice")[l_keep]
        * (100 - col(tables, "lineitem", "l_discount")[l_keep])
    )
    rev, n = sums(pos[l_keep], max(len(o_key), 1), revenue)
    hit = np.nonzero(n)[0]
    # order by revenue desc, o_orderdate
    top = hit[np.lexsort((o_date[hit], -rev[hit]))][:10]
    return [
        [int(o_key[g]), dec(rev[g], 4), int(o_date[g]), int(o_prio[g])]
        for g in top
    ]
