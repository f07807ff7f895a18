"""Deployment configurations: the DOD-ETL pipeline knobs (§3.1
"configuration process") plus each deployment's schema. Two schemas:

* ``steelworks`` -- the paper's case study (§4: production / equipment /
  quality tables, OEE KPIs), float32 lanes, facts appended;
* ``tpcdi-dimtrade`` -- TPC-DI v1.1.0's incremental update of DimTrade
  from Trade.txt CDC rows, joined point-in-time to a type-2 DimAccount
  and to a replicated DimSecurity, upserted by TradeID; int32 lanes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """Per-table deployment parameters (paper §3.1)."""

    name: str
    nature: str                 # "operational" | "master"
    row_key: str                # unique row identifier column
    business_key: str           # domain partition/filter column
    columns: Tuple[str, ...]    # payload schema (fixed-width numeric rows)
    lanes: str = "float32"      # payload dtype: "float32" | "int32" (ids,
                                # date keys and cents exact past 2**24)
    # master tables only. join_key: the payload lane the worker's cache is
    # keyed by (None: the table is not cached). scope: "partitioned" (each
    # worker holds the rows of its business keys) or "replicated" (every
    # worker holds the whole table). asof_lane: a type-2 table's
    # EffectiveDate lane -- the cache keeps every version and joins the
    # latest one in effect at the probe's time
    join_key: Optional[int] = None
    scope: str = "partitioned"
    asof_lane: Optional[int] = None
    slots: Optional[int] = None  # this table's cache slots (None: the
                                 # configuration's cache_slots)

    @property
    def width(self) -> int:
        return len(self.columns)


@dataclasses.dataclass(frozen=True)
class ETLConfig:
    """Full DOD-ETL deployment configuration."""

    tables: Tuple[TableConfig, ...]
    n_partitions: int = 20       # operational-topic partitions (paper: 20)
    n_business_keys: int = 20    # size of the business-key space (the
                                 # steelworks units; TPC-DI's accounts)
    cache_slots: int = 4096      # hash slots per in-memory master table
    buffer_capacity: int = 1024  # late-message ring buffer entries
    queue_retention: int = 1 << 20
    seed: int = 0
    backend: str = ""            # compute backend: "numpy" | "jax" | "pallas"
                                 # ("" = DODETL_BACKEND env var, else "jax")
    partition_strategy: str = "static"   # key->partition routing strategy:
                                 # "static" (hash%n), "consistent" (vnode
                                 # ring), "skew" (load-adaptive ranges)
    # --- concurrent runtime (repro.runtime.cluster.ConcurrentCluster) ---
    handoff_depth: int = 4       # bounded hand-off queue slots between the
                                 # ingest -> transform -> load worker stages
    idle_backoff_s: float = 0.001  # stage sleep when its input is drained
    credit_capacity: int = 4096  # per-worker flow-control credits (records):
                                 # ingest spends on fetch, load refunds at
                                 # commit — a stalled downstream exhausts the
                                 # ledger and throttles extraction
    # --- schema ---
    schema: str = "steelworks"   # the transform body: "steelworks" |
                                 # "tpcdi-dimtrade"
    # a worker pumps master changes only up to the newest operational
    # record it has fetched, so a replayed backlog meets its masters in
    # log order, as the live stream did (a master that landed after its
    # records holds them in the late buffer)
    masters_in_log_order: bool = False
    # the warehouse target: rows are appended (target_key None) or upserted
    # by column target_key in log order, an update keeping the old value
    # of each target_keep column where it carries NULL (-1)
    target_key: Optional[int] = None
    target_keep: Tuple[int, ...] = ()

    def table(self, name: str) -> TableConfig:
        for t in self.tables:
            if t.name == name:
                return t
        raise KeyError(name)

    @property
    def operational_tables(self) -> Tuple[TableConfig, ...]:
        return tuple(t for t in self.tables if t.nature == "operational")

    @property
    def master_tables(self) -> Tuple[TableConfig, ...]:
        return tuple(t for t in self.tables if t.nature == "master")

    @property
    def cached_tables(self) -> Tuple[TableConfig, ...]:
        """Master tables each worker caches, in the order the schema's
        transform takes their caches."""
        return tuple(t for t in self.master_tables if t.join_key is not None)


def steelworks_config(n_partitions: int = 20, complex_model: bool = False,
                      backend: str = "",
                      partition_strategy: str = "static",
                      n_units: Optional[int] = None) -> ETLConfig:
    """The paper's steelworks deployment (§4). ``n_units`` equipment units
    are the business keys; by default one per partition, the paper's
    layout.

    ``complex_model=True`` approximates the ISA-95 production workload of
    §4.1.4: each logical category is split across several normalized tables
    so the transform must perform deeper join chains.
    """
    if not complex_model:
        tables = (
            TableConfig("production", "operational", "prod_id", "equipment_id",
                        ("prod_id", "equipment_id", "txn_time", "t_start",
                         "t_end", "qty", "speed", "order_id")),
            TableConfig("equipment", "master", "equip_row_id", "equipment_id",
                        ("equip_row_id", "equipment_id", "txn_time", "t_start",
                         "t_end", "status", "max_speed", "planned"),
                        join_key=1),
            TableConfig("quality", "master", "qual_row_id", "equipment_id",
                        ("qual_row_id", "equipment_id", "txn_time", "prod_id",
                         "defects", "grade", "scrap", "rework"),
                        join_key=3),
        )
    else:
        # ISA-95-flavoured normalization: 9 tables, category split 3-ways;
        # the first equipment and the last quality table are cached in the
        # equipment and quality roles (the extra ones exercise join_depth)
        join_keys = {"equipment_segment": 1, "quality_detail": 3}
        tables = tuple(
            TableConfig(f"{cat}_{part}",
                        "operational" if cat == "production" else "master",
                        f"{cat}_{part}_row", "equipment_id",
                        (f"{cat}_{part}_row", "equipment_id", "txn_time",
                         "a", "b", "c", "d", "e"),
                        join_key=join_keys.get(f"{cat}_{part}"))
            for cat in ("production", "equipment", "quality")
            for part in ("segment", "event", "detail")
        )
    return ETLConfig(tables=tables, n_partitions=n_partitions,
                     n_business_keys=n_units or n_partitions,
                     backend=backend, partition_strategy=partition_strategy)


def tpcdi_dimtrade_config(n_accounts: int, n_partitions: int = 20,
                          backend: str = "") -> ETLConfig:
    """TPC-DI v1.1.0's incremental update of DimTrade from the Trade.txt
    CDC rows, as a streaming deployment: trades are partitioned by account (``T_CA_ID``),
    DimAccount versions are cached per worker (type-2, joined at the
    trade's ``T_DTS``) and DimSecurity is replicated into every worker.
    Symbols, executor names and the StatusType / TradeType ids are
    dictionary codes assigned at extraction; every lane is int32. Column
    layouts: ``repro.core.dimtrade``."""
    from repro.core import dimtrade as dt
    tables = (
        TableConfig("Trade", "operational", "t_id", "t_ca_id",
                    dt.TRADE_COLUMNS, lanes="int32"),
        TableConfig("DimAccount", "master", "sk_account_id", "account_id",
                    dt.ACCOUNT_COLUMNS, lanes="int32",
                    join_key=dt.ACCOUNT_COLUMNS.index("account_id"),
                    asof_lane=dt.ACCOUNT_COLUMNS.index("effective_date")),
        TableConfig("DimSecurity", "master", "sk_security_id", "symbol",
                    dt.SECURITY_COLUMNS, lanes="int32",
                    join_key=dt.SECURITY_COLUMNS.index("symbol"),
                    scope="replicated"),
    )
    return ETLConfig(
        tables=tables, n_partitions=n_partitions,
        n_business_keys=n_accounts, backend=backend, schema="tpcdi-dimtrade",
        masters_in_log_order=True,
        target_key=dt.DIMTRADE_COLUMNS.index("trade_id"),
        target_keep=dt.KEEP_ON_UPDATE)


# KPI definitions (paper §4): OEE = availability * performance * quality.
KPI_COLUMNS: Dict[str, int] = {
    "availability": 0,
    "performance": 1,
    "quality": 2,
    "oee": 3,
}
