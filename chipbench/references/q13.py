"""TPC-H Q13 (customer distribution): how many customers have how many
orders, those with none included; orders whose comment holds WORD1 and,
after it, WORD2 are not counted.

`customer LEFT OUTER JOIN orders ON c_custkey = o_custkey AND o_comment
NOT LIKE '%WORD1%WORD2%'` keeps every customer: one whose orders all
fail the ON clause, or who has none, comes out once with NULLs, and
`count(o_orderkey)` counts no NULL, so such a customer's count is 0.
Counted here with no join: the orders that pass are tallied by the
position of their customer's key among the customers' keys, block by
block, and the tallies are tallied again."""

import numpy as np

from chipbench.references._common import blocks, col, dict_values


def like(comment: str, word1: str, word2: str) -> bool:
    """comment like '%WORD1%WORD2%': WORD1 somewhere, WORD2 after its
    end (the leftmost WORD1 leaves WORD2 the most room)."""
    at = comment.find(word1)
    return at >= 0 and comment.find(word2, at + len(word1)) >= 0


def reference(tables, params, outer=True, count="o_orderkey", like_in="on",
              with_filter=True, order=("custdist", "c_count")):
    """The knobs are the controls' (`tests/chipbench/test_q13_cell.py`):
    each makes a reference that `correct` has to refuse."""
    comments = dict_values(tables, "orders", "o_comment")
    passes = np.asarray(
        [not (with_filter and like(v, params["word1"], params["word2"]))
         for v in comments], dtype=bool)
    c_key = np.sort(col(tables, "customer", "c_custkey"))
    # a customer key held twice is two customers, each with the key's orders
    first = np.searchsorted(c_key, c_key, side="left")
    o_cust = col(tables, "orders", "o_custkey")
    o_comment = col(tables, "orders", "o_comment")
    per_key = np.zeros(len(c_key), dtype=np.int64)
    for rows in blocks(len(o_cust)):
        keys = o_cust[rows][passes[o_comment[rows]]]
        pos = np.searchsorted(c_key, keys)
        pos[pos == len(c_key)] = 0
        pos = pos[c_key[pos] == keys] if len(c_key) else pos[:0]
        per_key += np.bincount(pos, minlength=len(c_key))
    c_count = per_key[first]
    if count == "*":
        # count(*) counts the row a customer without a pair comes out as
        c_count = np.maximum(c_count, 1)
    if not outer or like_in == "where":
        # an inner join has no such row; a WHERE over the outer join's
        # rows drops it too (NOT LIKE of a NULL is not true)
        c_count = c_count[per_key[first] > 0]
    custdist = np.bincount(c_count)
    out = [[int(n), int(custdist[n])] for n in np.nonzero(custdist)[0]]
    # order by custdist desc, c_count desc
    at = {"c_count": 0, "custdist": 1}
    return sorted(out, key=lambda r: (-r[at[order[0]]], -r[at[order[1]]]))
