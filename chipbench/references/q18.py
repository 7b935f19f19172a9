"""TPC-H Q18 (large volume customer): the orders whose lineitems sum to
more than QUANTITY, with their customer, the hundred of highest
o_totalprice."""

import numpy as np

from chipbench.references._common import blocks, col, dec, dict_values, group_sums

LIMIT = 100


def quantity_per_order(tables, sums=group_sums):
    """(o_orderkey sorted, the order's position in `orders`, its summed
    l_quantity in hundredths, its lineitem count), block by block: a
    block's lineitems find their order by key lookup and are summed
    over the span of orders the block touches."""
    o_key = col(tables, "orders", "o_orderkey")
    by_key = np.argsort(o_key, kind="stable")
    sorted_key = o_key[by_key]
    l_key = col(tables, "lineitem", "l_orderkey")
    l_qty = col(tables, "lineitem", "l_quantity")
    total = np.zeros(len(o_key), dtype=np.int64)
    lines = np.zeros(len(o_key), dtype=np.int64)
    for rows in blocks(len(l_key)):
        pos = np.searchsorted(sorted_key, l_key[rows])
        pos[pos == len(sorted_key)] = 0
        found = sorted_key[pos] == l_key[rows]
        pos, qty = pos[found], l_qty[rows][found]
        if not len(pos):
            continue
        lo, hi = int(pos.min()), int(pos.max()) + 1
        q, n = sums(pos - lo, hi - lo, qty)
        total[lo:hi] += np.asarray(q).astype(np.int64)
        lines[lo:hi] += n
    return sorted_key, by_key, total, lines


def reference(tables, params, sums=group_sums, having=np.greater):
    sorted_key, by_key, total, lines = quantity_per_order(tables, sums)
    # having sum(l_quantity) > QUANTITY; the IN set, and (one group an
    # order: o_orderkey is unique) the outer sum is the same number
    large = np.nonzero(
        (lines > 0) & having(total, int(params["quantity"]) * 100)
    )[0]
    o_row = by_key[large]
    o_cust = col(tables, "orders", "o_custkey")[o_row]
    c_key = col(tables, "customer", "c_custkey")
    c_by_key = np.argsort(c_key, kind="stable")
    c_pos = np.searchsorted(c_key[c_by_key], o_cust)
    c_pos[c_pos == len(c_key)] = 0
    has_customer = c_key[c_by_key][c_pos] == o_cust
    large, o_row = large[has_customer], o_row[has_customer]
    c_row = c_by_key[c_pos[has_customer]]
    price = col(tables, "orders", "o_totalprice")[o_row]
    date = col(tables, "orders", "o_orderdate")[o_row]
    # order by o_totalprice desc, o_orderdate
    ranked = np.lexsort((date, -price))
    head = ranked[:LIMIT + 1]
    tied = (np.diff(price[head]) == 0) & (np.diff(date[head]) == 0)
    assert not tied.any(), (
        "q18: two rows at the limit tie on o_totalprice and o_orderdate: "
        "the statement does not fix their order"
    )
    names = dict_values(tables, "customer", "c_name")
    c_name = col(tables, "customer", "c_name")
    return [
        [names[int(c_name[c_row[i]])], int(c_key[c_row[i]]),
         int(sorted_key[large[i]]), int(date[i]), dec(price[i], 2),
         dec(total[large[i]], 2)]
        for i in ranked[:LIMIT]
    ]
