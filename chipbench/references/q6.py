"""TPC-H Q6 (forecasting revenue change): one year from YEAR-01-01,
DISCOUNT +- 0.01, quantity under QUANTITY."""

import numpy as np

from chipbench.references._common import (
    add_sums, blocks, col, days, dec, group_sums,
)


def fields(params):
    d = params["discount_pct"]
    return {
        "date_lo": f"{params['year']}-01-01",
        "date_hi": f"{params['year'] + 1}-01-01",
        "disc_lo": f"0.{d - 1:02d}", "disc_hi": f"0.{d + 1:02d}",
    }


def reference(tables, params, sums=group_sums):
    f = fields(params)
    d = params["discount_pct"]
    sd, disc, qty, ep = (
        col(tables, "lineitem", c)
        for c in ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
    )
    total = None
    for rows in blocks(len(sd)):
        keep = (
            (sd[rows] >= days(f["date_lo"])) & (sd[rows] < days(f["date_hi"]))
            & (disc[rows] >= d - 1) & (disc[rows] <= d + 1)
            & (qty[rows] < params["quantity"] * 100)
        )
        revenue = ep[rows][keep] * disc[rows][keep]
        total = add_sums(
            total, sums(np.zeros(len(revenue), dtype=np.int64), 1, revenue)
        )
    return [[dec(total[0][0], 4)]]
