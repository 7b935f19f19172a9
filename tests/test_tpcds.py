"""TPC-DS connector + REAL query texts vs the sqlite oracle
(plugin/trino-tpcds analogue, SURVEY.md §2.12; VERDICT r1 item #8).

The queries below are the official TPC-DS templates q3/q7/q42/q43/q52/
q55/q65/q72/q82/q96 with parameter substitutions chosen to select rows
at tiny scale (parameter substitution is how the spec instantiates
templates). q72 is BASELINE config 4's deep multi-build join tree."""

import sqlite3

import pytest

from tests.oracle import assert_rows_match, load_tpcds_sqlite, sqlite_rows
from trino_tpu.connectors.tpcds import row_count

SF = 0.01


@pytest.fixture(scope="module")
def oracle():
    conn = sqlite3.connect(":memory:")
    load_tpcds_sqlite(conn, SF)
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def runner(tpcds_local):
    return tpcds_local


def test_row_counts(runner):
    assert runner.execute("SELECT count(*) FROM store_sales").only_value() == row_count("store_sales", SF)
    assert runner.execute("SELECT count(*) FROM inventory").only_value() == row_count("inventory", SF)
    assert runner.execute("SELECT count(*) FROM catalog_sales").only_value() == row_count("catalog_sales", SF)


QUERIES = {
    "q3": """
    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = 436
      and dt.d_moy = 12
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100
    """,
    "q7": """
    select i_item_id,
           avg(ss_quantity) agg1, avg(ss_list_price) agg2,
           avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4
    from store_sales, customer_demographics, date_dim, item, promotion
    where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
      and ss_cdemo_sk = cd_demo_sk and ss_promo_sk = p_promo_sk
      and cd_gender = 'M' and cd_marital_status = 'S'
      and cd_education_status = 'College'
      and (p_channel_email = 'N' or p_channel_event = 'N')
      and d_year = 2000
    group by i_item_id
    order by i_item_id
    limit 100
    """,
    "q42": """
    select dt.d_year, item.i_category_id, item.i_category,
           sum(ss_ext_sales_price)
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manager_id = 1
      and dt.d_moy = 11 and dt.d_year = 2000
    group by dt.d_year, item.i_category_id, item.i_category
    order by sum(ss_ext_sales_price) desc, dt.d_year,
             item.i_category_id, item.i_category
    limit 100
    """,
    "q43": """
    select s_store_name, s_store_id,
      sum(case when (d_day_name = 'Sunday') then ss_sales_price else null end) sun_sales,
      sum(case when (d_day_name = 'Monday') then ss_sales_price else null end) mon_sales,
      sum(case when (d_day_name = 'Tuesday') then ss_sales_price else null end) tue_sales,
      sum(case when (d_day_name = 'Wednesday') then ss_sales_price else null end) wed_sales,
      sum(case when (d_day_name = 'Thursday') then ss_sales_price else null end) thu_sales,
      sum(case when (d_day_name = 'Friday') then ss_sales_price else null end) fri_sales,
      sum(case when (d_day_name = 'Saturday') then ss_sales_price else null end) sat_sales
    from date_dim, store_sales, store
    where d_date_sk = ss_sold_date_sk and s_store_sk = ss_store_sk
      and s_gmt_offset = -5 and d_year = 2000
    group by s_store_name, s_store_id
    order by s_store_name, s_store_id, sun_sales, mon_sales, tue_sales,
             wed_sales, thu_sales, fri_sales, sat_sales
    limit 100
    """,
    "q52": """
    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manager_id = 1
      and dt.d_moy = 11 and dt.d_year = 2000
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, ext_price desc, brand_id
    limit 100
    """,
    "q55": """
    select i_brand_id brand_id, i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand, i_brand_id
    order by ext_price desc, brand_id
    limit 100
    """,
    "q65": """
    select s_store_name, i_item_desc, sc.revenue, i_current_price
    from store, item,
         (select ss_store_sk, avg(revenue) as ave
          from (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
                from store_sales, date_dim
                where ss_sold_date_sk = d_date_sk
                  and d_month_seq between 1176 and 1176 + 11
                group by ss_store_sk, ss_item_sk) sa
          group by ss_store_sk) sb,
         (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
          from store_sales, date_dim
          where ss_sold_date_sk = d_date_sk
            and d_month_seq between 1176 and 1176 + 11
          group by ss_store_sk, ss_item_sk) sc
    where sb.ss_store_sk = sc.ss_store_sk
      and sc.revenue <= 0.1 * sb.ave
      and s_store_sk = sc.ss_store_sk
      and i_item_sk = sc.ss_item_sk
    order by s_store_name, i_item_desc, sc.revenue
    limit 100
    """,
    "q72": """
    select i_item_desc, w_warehouse_name, d1.d_week_seq,
      sum(case when p_promo_sk is null then 1 else 0 end) no_promo,
      sum(case when p_promo_sk is not null then 1 else 0 end) promo,
      count(*) total_cnt
    from catalog_sales
    join inventory on (cs_item_sk = inv_item_sk)
    join warehouse on (w_warehouse_sk = inv_warehouse_sk)
    join item on (i_item_sk = cs_item_sk)
    join customer_demographics on (cs_bill_cdemo_sk = cd_demo_sk)
    join household_demographics on (cs_bill_hdemo_sk = hd_demo_sk)
    join date_dim d1 on (cs_sold_date_sk = d1.d_date_sk)
    join date_dim d2 on (inv_date_sk = d2.d_date_sk)
    join date_dim d3 on (cs_ship_date_sk = d3.d_date_sk)
    left outer join promotion on (cs_promo_sk = p_promo_sk)
    left outer join catalog_returns on (cr_item_sk = cs_item_sk
                                        and cr_order_number = cs_order_number)
    where d1.d_week_seq = d2.d_week_seq
      and inv_quantity_on_hand < cs_quantity
      and d3.d_date > d1.d_date + 5
      and hd_buy_potential = '>10000'
      and d1.d_year = 1999
      and cd_marital_status = 'D'
    group by i_item_desc, w_warehouse_name, d1.d_week_seq
    order by total_cnt desc, i_item_desc, w_warehouse_name, d1.d_week_seq
    limit 100
    """,
    # ^ spec text says bare `d_week_seq`, which the standard resolves to
    # the OUTPUT column; sqlite (the oracle) instead reports ambiguity
    # against d1/d2/d3, so the template qualifies it — same plan shape
    "q82": """
    select i_item_id, i_item_desc, i_current_price
    from item, inventory, date_dim, store_sales
    where i_current_price between 30 and 30 + 30
      and inv_item_sk = i_item_sk
      and d_date_sk = inv_date_sk
      and d_date between date '2002-05-30' and date '2002-07-29'
      and i_manufact_id in (437, 129, 727, 663)
      and inv_quantity_on_hand between 100 and 500
      and ss_item_sk = i_item_sk
    group by i_item_id, i_item_desc, i_current_price
    order by i_item_id
    limit 100
    """,
    "q96": """
    select count(*)
    from store_sales, household_demographics, time_dim, store
    where ss_sold_time_sk = time_dim.t_time_sk
      and ss_hdemo_sk = household_demographics.hd_demo_sk
      and ss_store_sk = s_store_sk
      and time_dim.t_hour = 20
      and time_dim.t_minute >= 30
      and household_demographics.hd_dep_count = 7
      and store.s_store_name = 'ese'
    """,
}

# queries that must select rows at tiny scale for the test to mean
# anything; parameters below are re-substituted from live data
_NONEMPTY = {"q3", "q7", "q42", "q43", "q52", "q55", "q72", "q82"}


def _sql_for(name, oracle):
    """Parameter substitution against the generated data (the spec
    instantiates templates the same way)."""
    sql = QUERIES[name]
    if name == "q96":
        (store_name,) = oracle.execute(
            "select s_store_name from store limit 1"
        ).fetchone()
        sql = sql.replace("'ese'", f"'{store_name}'")
    if name in ("q42", "q52"):
        (mgr,) = oracle.execute(
            "select i_manager_id from item group by i_manager_id"
            " order by count(*) desc limit 1"
        ).fetchone()
        sql = sql.replace("i_manager_id = 1", f"i_manager_id = {mgr}")
    if name == "q3":
        (mfg,) = oracle.execute(
            "select i_manufact_id from item group by i_manufact_id"
            " order by count(*) desc limit 1"
        ).fetchone()
        sql = sql.replace("i_manufact_id = 436", f"i_manufact_id = {mfg}")
    if name == "q82":
        ids = [
            str(r[0])
            for r in oracle.execute(
                "select distinct i_manufact_id from item"
                " where i_current_price between 30 and 60 limit 4"
            )
        ]
        sql = sql.replace("437, 129, 727, 663", ", ".join(ids) or "437")
    return sql


def _oracle_rows(oracle, sql):
    from tests.test_tpch import to_sqlite

    return sqlite_rows(oracle, to_sqlite(sql))


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_tpcds_query(name, runner, oracle):
    sql = _sql_for(name, oracle)
    got = runner.execute(sql).rows
    want = _oracle_rows(oracle, sql)
    if name in _NONEMPTY:
        assert want, f"{name}: oracle selected no rows at tiny scale"
    assert_rows_match(got, want, ordered=("order by" in sql), abs_tol=1e-2)


@pytest.mark.parametrize(
    "name",
    [
        "q3",
        # q72 distributed compiles ~6 min of XLA programs on a cold CPU
        # cache and was the single largest tier-1 wall-clock item (the
        # full suite overran its budget even before PR 5); it keeps
        # single-node oracle coverage above and distributed coverage in
        # the slow tier
        pytest.param("q72", marks=pytest.mark.slow),
    ],
)
def test_tpcds_distributed(name, oracle, tpcds_cluster):
    r = tpcds_cluster
    sql = _sql_for(name, oracle)
    got = r.execute(sql).rows
    want = _oracle_rows(oracle, sql)
    assert_rows_match(got, want, ordered=("order by" in sql), abs_tol=1e-2)
