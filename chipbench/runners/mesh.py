"""Runner kind `mesh`: one colocated DistributedQueryRunner over the
memory connector, one worker and one hash partition per chip, session
defaults (the mesh plane itself decides from the sizes whether a scan is
streamed in chunks). Every statement goes through its `execute` to the
mesh plane: the tables are dealt over the devices shard by shard
(`trino_tpu/parallel/mesh_feed.py`), no device holds one whole. A
statement that leaves the mesh plane fails (the runner's
`on_mesh_fallback`): the page exchange could not hold these tables in
any time a run has.

A program without that module stages every scan of every statement
through device 0 and the host; it cannot hold this kind's tables, so
`build` refuses it at once instead of letting it try for an hour."""


def build(config: dict, tables):
    try:
        from trino_tpu.parallel import mesh_feed  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: runner kind 'mesh' needs trino_tpu/parallel/"
            "mesh_feed.py (tables dealt over the devices); this program "
            "has none and cannot hold the configuration's tables"
        ) from None
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES
    from trino_tpu.engine import Session
    from trino_tpu.runtime import DistributedQueryRunner

    mem = create_memory_connector()
    for table, cols in tables.items():
        types = dict(TABLES[table])
        mem.load_table(
            config["schema"], table,
            [ColumnMetadata(n, types[n]) for n in cols],
            [data for data, _ in cols.values()], None,
            [d for _, d in cols.values()],
        )
    runner = DistributedQueryRunner(
        Session(catalog=config["connector"], schema=config["schema"],
                batch_rows=config["batch_rows"]),
        n_workers=config["chips"], hash_partitions=config["chips"],
    )
    runner.register_catalog(config["connector"], mem)

    def refuse(reason: str) -> None:
        raise RuntimeError(
            f"chipbench: a statement left the mesh plane ({reason}); at this "
            "scale the page exchange would take the run's time limit"
        )

    # the fallback is counted and logged first; the statement then fails
    # here, at once, and the run comes out not correct
    runner.on_mesh_fallback = refuse
    return runner
