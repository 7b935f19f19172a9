"""TPC-H Q1 (pricing summary report), DELTA days before 1998-12-01."""

import numpy as np

from chipbench.references._common import (
    add_sums, avg2, blocks, col, days, dec, dict_values, group_sums,
)


def reference(tables, params, sums=group_sums):
    rf_names = dict_values(tables, "lineitem", "l_returnflag")
    ls_names = dict_values(tables, "lineitem", "l_linestatus")
    cutoff = days("1998-12-01") - params["delta"]
    rf, ls, sd, qty, ep, disc, tax = (
        col(tables, "lineitem", c)
        for c in ("l_returnflag", "l_linestatus", "l_shipdate", "l_quantity",
                  "l_extendedprice", "l_discount", "l_tax")
    )
    # rows the predicate drops go to one more group, which is dropped
    n_groups = len(rf_names) * len(ls_names)
    total = None
    for rows in blocks(len(sd)):
        disc_price = ep[rows] * (100 - disc[rows])
        codes = np.where(
            sd[rows] <= cutoff,
            rf[rows].astype(np.int64) * len(ls_names) + ls[rows], n_groups,
        )
        total = add_sums(total, sums(
            codes, n_groups + 1,
            qty[rows], ep[rows], disc_price, disc_price * (100 + tax[rows]),
            disc[rows],
        ))
    s_qty, s_ep, s_dp, s_ch, s_disc, n = (s[:n_groups] for s in total)
    rows = []
    for g in np.nonzero(n)[0]:
        rows.append([
            rf_names[g // len(ls_names)], ls_names[g % len(ls_names)],
            dec(s_qty[g], 2), dec(s_ep[g], 2), dec(s_dp[g], 4),
            dec(s_ch[g], 6), avg2(s_qty[g], n[g]), avg2(s_ep[g], n[g]),
            avg2(s_disc[g], n[g]), int(n[g]),
        ])
    return sorted(rows, key=lambda r: (r[0], r[1]))
