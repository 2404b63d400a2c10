"""Session: the SparkSession-shaped entry point."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.utils import lockorder


class Session:
    """Holds the config snapshot and builds root DataFrames. The
    reference's SQLPlugin injects itself into a SparkSession; here the
    Session IS the host (standalone framework), and acceleration gates
    ride the same rapids.tpu.* keys."""

    def __init__(self, conf: Optional[Dict] = None,
                 initialize_runtime: bool = False):
        self.conf = conf if isinstance(conf, RapidsConf) else \
            RapidsConf(conf)
        if initialize_runtime:
            # executor-init analogue: device acquisition, HBM budget,
            # global spill catalog + semaphore (runtime/device.py).
            # The runtime is PROCESS-GLOBAL (one chip, one catalog):
            # initializing a second Session replaces it, so refuse while
            # another Session still owns it — stop() that one first.
            from spark_rapids_tpu import runtime

            current = runtime.get_env()
            if current is not None and \
                    getattr(current, "_owner", None) is not None:
                raise RuntimeError(
                    "another Session owns the runtime; call its "
                    ".stop() before initializing a new one")
            self.runtime = runtime.initialize(self.conf)
            self.runtime._owner = self
        else:
            self.runtime = None
        self._catalog: Dict = {}
        #: table name -> registration version; replacing a temp view
        #: bumps it (a SNAPSHOT EVENT for the semantic cache)
        self._catalog_versions: Dict[str, int] = {}
        self._service = None
        import threading

        self._service_init_lock = lockorder.make_lock("api.session.serviceInit")

    @property
    def service(self):
        """Lazily-started concurrent query service (service/) — the
        multi-tenant front door. ``df.collect_async()`` and
        ``sql_async()`` submit through it."""
        with self._service_init_lock:
            if self._service is None:
                if getattr(self, "_service_stopped", False):
                    # stop() tore the service (and runtime) down —
                    # lazily resurrecting a fresh worker pool against
                    # it would "succeed" into a dead engine and leak
                    # threads
                    raise RuntimeError(
                        "Session is stopped; create a new Session")
                from spark_rapids_tpu.service import QueryService

                self._service = QueryService(self.conf, session=self)
            return self._service

    def sql_async(self, query: str, tenant: str = "default",
                  priority: int = 0, deadline=None):
        """Parse + plan + submit to the query service; returns a
        QueryHandle (poll/result/cancel) instead of blocking."""
        return self.service.submit(self.sql(query), tenant=tenant,
                                   priority=priority, deadline=deadline)

    def stop(self) -> None:
        """Release the process-global runtime this Session initialized
        (SparkSession.stop analogue) and shut down the query service.
        No-op for sessions that did not initialize them."""
        with self._service_init_lock:
            self._service_stopped = True
            service, self._service = self._service, None
        if service is not None:
            service.shutdown()
        if self.runtime is None:
            return
        from spark_rapids_tpu import runtime

        if runtime.get_env() is self.runtime:
            runtime.shutdown()
        self.runtime = None

    # -- readers ----------------------------------------------------------

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    def create_dataframe(self, data, schema: Optional[Schema] = None
                         ) -> DataFrame:
        """From a pandas DataFrame or a dict of columns."""
        import pandas as pd

        if isinstance(data, pd.DataFrame):
            cols = {}
            validity = {}
            for name in data.columns:
                s = data[name]
                if s.dtype == object or str(s.dtype) == "string":
                    cols[name] = np.array(
                        [None if v is None or (isinstance(v, float) and
                                               np.isnan(v)) else v
                         for v in s], dtype=object)
                else:
                    isna = s.isna().to_numpy(dtype=bool)
                    cols[name] = s.fillna(0).to_numpy()
                    if isna.any():
                        validity[name] = ~isna
            src = pn.InMemorySource(cols, schema=schema,
                                    validity=validity)
        else:
            src = pn.InMemorySource(dict(data), schema=schema)
        return DataFrame(pn.ScanNode(src), self)

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(pn.RangeNode(start, end, step), self)

    # -- SQL entry point ---------------------------------------------------

    def create_temp_view(self, name: str, df_or_source) -> int:
        """Register a DataFrame / DataSource / plan under ``name`` for
        Session.sql (createOrReplaceTempView analogue). REPLACING a
        registered view is a SNAPSHOT EVENT: the displaced target's
        sources get their cache snapshot version bumped, so results the
        semantic cache computed from the old view are unreachable (the
        version participates in every cache key) — a silent replace
        must never serve yesterday's dashboard. Returns the table's new
        registration version."""
        target = df_or_source
        if isinstance(target, DataFrame):
            target = target._plan
        prev = self._catalog.get(name)
        if prev is not None and prev is not target:
            from spark_rapids_tpu.service.cache import snapshots

            snapshots.bump_plan(prev)
        self._catalog[name] = target
        version = self._catalog_versions.get(name, 0) + 1
        self._catalog_versions[name] = version
        return version

    createOrReplaceTempView = create_temp_view

    def table_version(self, name: str) -> int:
        """Registration version of ``name`` (0 = never registered)."""
        return self._catalog_versions.get(name, 0)

    def bump_table_version(self, name: str) -> int:
        """Explicitly invalidate cached results over ``name`` (the
        in-place-mutation escape hatch: data changed UNDER the same
        registered source object, which no key can see on its own)."""
        from spark_rapids_tpu.service.cache import snapshots

        target = self._catalog.get(name)
        if target is not None:
            snapshots.bump_plan(target)
        version = self._catalog_versions.get(name, 0) + 1
        self._catalog_versions[name] = version
        return version

    # -- streaming tables (service/streaming) ------------------------------

    def create_streaming_table(self, name: str, schema: Schema):
        """Create an appendable streaming table, register it as a temp
        view (batch queries over it see all rows appended so far), and
        return the StreamTableSource. Feed it with ``append_batch``;
        register continuous aggregations over it with
        ``service.register_standing``."""
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.service.streaming.source import \
            StreamTableSource

        src = StreamTableSource(name, schema)
        if str(self.conf.get(cfg.STREAMING_CHECKPOINT_DIR)
               or "").strip():
            # durability (PR 19): replay the table's WAL and route
            # future appends through it — BEFORE the view registers,
            # so batch queries see recovered rows from the first scan.
            # The knob check keeps the lazy `service` property lazy for
            # non-durable sessions.
            self.service.streaming.attach_source(src)
        self.create_temp_view(name, src)
        return src

    def streaming_table(self, name: str):
        """The registered StreamTableSource behind ``name``."""
        from spark_rapids_tpu.plan.incremental import \
            is_streaming_source

        target = self._catalog.get(name)
        if isinstance(target, pn.ScanNode):
            target = target.source
        if target is None or not is_streaming_source(target):
            raise KeyError(f"{name!r} is not a registered streaming "
                           "table")
        return target

    def append_batch(self, table, data, validity=None) -> int:
        """Append one micro-batch (dict of columns or pandas frame) to
        a streaming table — by name or source — routing through the
        query service so standing queries fold it synchronously;
        returns the rows landed."""
        return self.service.ingest(table, data, validity)

    def register_parquet(self, name: str, path, columns=None) -> None:
        """Catalog a parquet directory as a SQL table."""
        from spark_rapids_tpu.io import ParquetSource

        self.create_temp_view(name, ParquetSource(path, columns=columns))

    def sql(self, query: str) -> DataFrame:
        """Parse + plan a SELECT over the catalog; returns a lazy
        DataFrame like any other (the whole override/oracle machinery
        downstream is shared). Unsupported SQL raises SqlError."""
        from spark_rapids_tpu.sql import parse, plan_statement
        from spark_rapids_tpu.utils.tracing import TraceRange

        with TraceRange("sql.parse"):
            statement = parse(query)
        with TraceRange("sql.plan"):
            plan = plan_statement(statement, self._catalog)
        return DataFrame(plan, self)


class DataFrameReader:
    def __init__(self, session: Session):
        self.session = session

    def parquet(self, *paths, columns=None) -> DataFrame:
        from spark_rapids_tpu.io import ParquetSource

        src = ParquetSource(list(paths) if len(paths) > 1 else paths[0],
                            columns=columns, conf=self.session.conf)
        return DataFrame(pn.ScanNode(src), self.session)

    def orc(self, *paths, columns=None) -> DataFrame:
        from spark_rapids_tpu.io import OrcSource

        src = OrcSource(list(paths) if len(paths) > 1 else paths[0],
                        columns=columns, conf=self.session.conf)
        return DataFrame(pn.ScanNode(src), self.session)

    def csv(self, *paths, schema: Optional[Schema] = None,
            header: bool = True, delimiter: str = ",") -> DataFrame:
        from spark_rapids_tpu.io import CsvSource

        src = CsvSource(list(paths) if len(paths) > 1 else paths[0],
                        schema=schema, header=header,
                        delimiter=delimiter, conf=self.session.conf)
        return DataFrame(pn.ScanNode(src), self.session)
