"""TPC-H Q21 (suppliers who kept orders waiting): for the suppliers of
NATION, the late lines of theirs on F orders that have other suppliers
too and on which no other supplier was late; the hundred suppliers with
the most such lines.

Counted per order, with no join of the fact table to itself. A line l1
qualifies iff it is late (receipt after commit), its order's status is
F, its supplier is of the nation, and
- `exists (l2: same order, l2.l_suppkey <> l1.l_suppkey)`: the order's
  lines have at least 2 distinct suppliers (one of them is l1's, so
  another line has another);
- `not exists (l3: same order, l3.l_suppkey <> l1.l_suppkey, l3 late)`:
  every late line of the order has l1's supplier; l1 is late itself, so
  the order's LATE lines have exactly 1 distinct supplier, l1's own.
Whether a set of supplier keys holds one value or several is read off
three exact sums over the set, its size n, the keys' sum and the sum of
their squares: n * sum(s^2) = sum(s)^2 iff all s are equal
(Cauchy-Schwarz). `count(*)` counts the qualifying LINES of a supplier,
not its orders."""

import numpy as np

from chipbench.references._common import blocks, col, dict_values, group_sums

LIMIT = 100


def order_position(sorted_key, keys):
    """(position of each key in `sorted_key`, whether it is there)."""
    pos = np.searchsorted(sorted_key, keys)
    pos[pos == len(sorted_key)] = 0
    found = sorted_key[pos] == keys if len(sorted_key) else np.zeros(len(keys), bool)
    return pos, found


def suppliers_per_order(tables, sorted_key, late_only, sums=group_sums):
    """(lines, sum of l_suppkey, sum of its square) per order, over all
    of an order's lines or over its late ones, block by block: a
    block's lines are summed over the span of orders the block touches."""
    l_key = col(tables, "lineitem", "l_orderkey")
    l_supp = col(tables, "lineitem", "l_suppkey")
    n = np.zeros(len(sorted_key), dtype=np.int64)
    s1, s2 = np.zeros_like(n), np.zeros_like(n)
    for rows in blocks(len(l_key)):
        pos, keep = order_position(sorted_key, l_key[rows])
        if late_only:
            keep &= (col(tables, "lineitem", "l_receiptdate")[rows]
                     > col(tables, "lineitem", "l_commitdate")[rows])
        pos, supp = pos[keep], l_supp[rows][keep].astype(np.int64)
        if not len(pos):
            continue
        lo, hi = int(pos.min()), int(pos.max()) + 1
        a, b, c = sums(pos - lo, hi - lo, supp, supp * supp)
        s1[lo:hi] += a
        s2[lo:hi] += b
        n[lo:hi] += c
    return n, s1, s2


def several(n, s1, s2):
    """Whether a set with these sums holds at least 2 distinct values."""
    return n * s2 != s1 * s1


def reference(tables, params, exists_other=True, not_exists_other=True,
              not_exists_late=True, with_status=True, count="lines",
              ties_ascending=True):
    """The knobs are the controls' (`tests/chipbench/test_q21_cell.py`):
    each makes a reference that `correct` has to refuse."""
    o_key = col(tables, "orders", "o_orderkey")
    by_key = np.argsort(o_key, kind="stable")
    sorted_key = o_key[by_key]
    status = col(tables, "orders", "o_orderstatus")[by_key]
    is_f = status == dict_values(tables, "orders", "o_orderstatus").index("F")
    if not with_status:
        is_f = np.ones_like(is_f)
    n_all, a1, a2 = suppliers_per_order(tables, sorted_key, late_only=False)
    n_late, b1, b2 = suppliers_per_order(tables, sorted_key, late_only=not_exists_late)
    # exists: another supplier's line (without the <>: any line, l1 itself)
    has_other = several(n_all, a1, a2) if exists_other else n_all > 0
    # not exists: no (late) line of another supplier (without the <>: no
    # (late) line at all)
    none_other = ~several(n_late, b1, b2) if not_exists_other else n_late == 0
    order_ok = is_f & has_other & none_other

    n_names = dict_values(tables, "nation", "n_name")
    n_code = col(tables, "nation", "n_name")
    nation_keys = col(tables, "nation", "n_nationkey")[
        n_code == n_names.index(params["nation"])]
    s_key = col(tables, "supplier", "s_suppkey")
    s_by_key = np.argsort(s_key, kind="stable")
    s_sorted = s_key[s_by_key]
    s_of_nation = np.isin(col(tables, "supplier", "s_nationkey")[s_by_key], nation_keys)

    l_key = col(tables, "lineitem", "l_orderkey")
    l_supp = col(tables, "lineitem", "l_suppkey")
    waits = np.zeros(len(s_key), dtype=np.int64)
    pairs = []
    for rows in blocks(len(l_key)):
        pos, keep = order_position(sorted_key, l_key[rows])
        keep &= (col(tables, "lineitem", "l_receiptdate")[rows]
                 > col(tables, "lineitem", "l_commitdate")[rows])
        keep &= order_ok[pos]
        s_pos, found = order_position(s_sorted, l_supp[rows])
        keep &= found & s_of_nation[s_pos]
        if count == "lines":
            waits += np.bincount(s_pos[keep], minlength=len(s_key))
        else:
            pairs.append(np.stack([s_pos[keep], pos[keep]], axis=1))
    if count != "lines":
        distinct = np.unique(np.concatenate(pairs), axis=0)
        waits = np.bincount(distinct[:, 0], minlength=len(s_key))

    # group by s_name: the names' codes (equal names, if any, are one group)
    name_code = col(tables, "supplier", "s_name")[s_by_key]
    names = dict_values(tables, "supplier", "s_name")
    per_name = np.bincount(name_code, waits, minlength=len(names)).astype(np.int64)
    groups = np.nonzero(per_name)[0]
    out = [[names[int(g)], int(per_name[g])] for g in groups]
    # order by numwait desc, s_name
    out.sort(key=lambda r: r[0], reverse=not ties_ascending)
    out.sort(key=lambda r: -r[1])
    return out[:LIMIT]
