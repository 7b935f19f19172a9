"""Runner kind `local`: one LocalQueryRunner over the memory connector,
at the configuration's batch size. The harness serves whatever `build`
returns behind a CoordinatorServer; a runner kind that needs more (a
mesh, replicas) is a new file here."""


def build(config: dict, tables):
    from trino_tpu.connectors.memory import create_memory_connector
    from trino_tpu.connectors.spi import ColumnMetadata
    from trino_tpu.connectors.tpch import TABLES
    from trino_tpu.engine import LocalQueryRunner, Session

    mem = create_memory_connector()
    for table, cols in tables.items():
        types = dict(TABLES[table])
        mem.load_table(
            config["schema"], table,
            [ColumnMetadata(n, types[n]) for n in cols],
            [data for data, _ in cols.values()], None,
            [d for _, d in cols.values()],
        )
    runner = LocalQueryRunner(Session(
        catalog=config["connector"], schema=config["schema"],
        batch_rows=config["batch_rows"],
    ))
    runner.register_catalog(config["connector"], mem)
    return runner
