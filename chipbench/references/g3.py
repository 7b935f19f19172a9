"""G3: a three-key rollup on lineitem (shipmode x shipinstruct x
returnflag, 160 key slots). Not a TPC-H query: it is the repo's one
join-free statement that reaches the Pallas `grouped_sum_mxu`."""

import numpy as np

from chipbench.references._common import (
    add_sums, blocks, col, dec, dict_values, group_sums,
)

KEYS = ("l_shipmode", "l_shipinstruct", "l_returnflag")


def reference(tables, params, sums=group_sums):
    names = [dict_values(tables, "lineitem", c) for c in KEYS]
    keys = [col(tables, "lineitem", c) for c in KEYS]
    quantity = col(tables, "lineitem", "l_quantity")
    n_groups = len(names[0]) * len(names[1]) * len(names[2])
    total = None
    for rows in blocks(len(quantity)):
        codes = np.zeros(len(quantity[rows]), dtype=np.int64)
        for key, vals in zip(keys, names):
            codes = codes * len(vals) + key[rows]
        total = add_sums(total, sums(codes, n_groups, quantity[rows]))
    qty, n = total
    rows = []
    for g in np.nonzero(n)[0]:
        a, rem = divmod(int(g), len(names[1]) * len(names[2]))
        b, c = divmod(rem, len(names[2]))
        rows.append([names[0][a], names[1][b], names[2][c],
                     int(n[g]), dec(qty[g], 2)])
    return rows
