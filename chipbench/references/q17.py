"""TPC-H Q17 (small-quantity-order revenue): the yearly revenue lost if
the orders of one brand's parts in one container were no longer taken
where they ask for less than a fifth of the part's average quantity.

`l_quantity < (select 0.2 * avg(l_quantity) from lineitem where
l_partkey = p_partkey)` compares every line of a selected part with an
aggregate of that part's OWN lines, all of them, whatever their
quantity. Computed here with no join and no float: the selected parts'
keys are sorted, every line finds its part by position, block by block,
and a part's quantities are summed and counted as integers (hundredths).
`avg` over decimal(12,2) is a decimal(12,2): the quotient rounded half up
at the cent, `(2 * sum + n) // (2 * n)`. `0.2 * avg` is a decimal(14,3),
exactly `2 * avg` thousandths, and a quantity of `q` hundredths is below
it where `10 * q < 2 * avg`. The lines that are add their extended price;
the sum, a decimal(38,2), over 7.0 is a decimal of scale 2 rounded half
up, `(2 * sum + 7) // 14` hundredths; no line at all leaves a NULL."""

import numpy as np

from chipbench.references._common import blocks, col, dec, dict_values


def selected_parts(tables, brand, container):
    """The keys of the parts of `brand` in `container`, sorted."""
    brands = dict_values(tables, "part", "p_brand")
    containers = dict_values(tables, "part", "p_container")
    if brand not in brands or container not in containers:
        return np.zeros(0, dtype=np.int64)
    keep = ((col(tables, "part", "p_brand") == brands.index(brand))
            & (col(tables, "part", "p_container") == containers.index(container)))
    return np.sort(col(tables, "part", "p_partkey")[keep])


def lines_of(keys, partkey):
    """(rows of `partkey` whose part is one of `keys`, the part's
    position among `keys`)."""
    if not len(keys):
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    pos = np.searchsorted(keys, partkey)
    pos[pos == len(keys)] = 0
    found = np.nonzero(keys[pos] == partkey)[0]
    return found, pos[found]


def reference(tables, params, average="decimal", compare=np.less, over="part",
              scale_first=False, yearly=True, revenue_dtype=np.int64):
    """The knobs are the controls' (`tests/chipbench/test_q17_cell.py`):
    each makes a reference that `correct` has to refuse."""
    keys = selected_parts(tables, params["brand"], params["container"])
    assert (np.diff(keys) > 0).all(), "q17: p_partkey is not a key of part"
    l_part = col(tables, "lineitem", "l_partkey")
    l_qty = col(tables, "lineitem", "l_quantity")
    l_price = col(tables, "lineitem", "l_extendedprice")

    def quantities(keep=None):
        """(sum of the quantities, number) of each selected part's lines,
        of those `keep(part's position, quantity)` says where given."""
        total = np.zeros(len(keys), dtype=np.int64)
        lines = np.zeros(len(keys), dtype=np.int64)
        for rows in blocks(len(l_part)):
            found, pos = lines_of(keys, l_part[rows])
            qty = l_qty[rows][found].astype(np.int64)
            if keep is not None:
                kept = keep(pos, qty)
                pos, qty = pos[kept], qty[kept]
            np.add.at(total, pos, qty)
            np.add.at(lines, pos, 1)
        return total, lines

    def fifth_of_average(total, lines):
        """0.2 * avg(l_quantity) in thousandths, a part."""
        n = np.maximum(lines, 1)
        if average == "float32":
            return np.float32(2.0) * (total.astype(np.float32) / n.astype(np.float32))
        if scale_first:
            # 0.2 * (sum / n) rounded once, at the thousandth
            return (4 * total + n) // (2 * n)
        if average == "truncated":
            return 2 * (total // n)
        return 2 * ((2 * total + n) // (2 * n))

    total, lines = quantities()
    if over == "all":
        # one average for the whole table, not one a part
        total[:] = sum(int(l_qty[rows].astype(np.int64).sum())
                       for rows in blocks(len(l_qty)))
        lines[:] = len(l_qty)

    def below(lines, limit):
        """Which (part's position, quantity) pairs are lines of a part
        that has lines, below a fifth of its average."""
        return lambda pos, qty: (lines[pos] > 0) & compare(10 * qty, limit[pos])

    keep = below(lines, fifth_of_average(total, lines))
    if over == "kept":
        # the average of the lines the filter keeps, and the filter again
        total, lines = quantities(keep)
        keep = below(lines, fifth_of_average(total, lines))

    revenue, any_line = 0, False
    for rows in blocks(len(l_part)):
        found, pos = lines_of(keys, l_part[rows])
        ok = keep(pos, l_qty[rows][found].astype(np.int64))
        revenue = revenue + l_price[rows][found][ok].astype(revenue_dtype).sum(
            dtype=revenue_dtype)
        any_line = any_line or bool(ok.any())
    if not any_line:
        return [[None]]
    revenue = int(revenue)
    if not yearly:
        return [[dec(revenue, 2)]]
    return [[dec((2 * revenue + 7) // 14, 2)]]
