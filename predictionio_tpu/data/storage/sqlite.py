"""SQLite storage backend — the SQL (JDBC-analog) backend.

Capability parity with the reference JDBC backend
(storage/jdbc/src/main/scala/org/apache/predictionio/data/storage/jdbc/):
metadata DAOs, per-app event tables named ``pio_event_<appId>[_<channel>]``
(JDBCLEvents.scala:37), and a models table. SQLite is the embedded default
(the reference defaults to PGSQL); the DAO contract keeps any SQL engine
pluggable behind the same registry.
"""

from __future__ import annotations

import json
import sqlite3
import threading
import uuid
from datetime import datetime, timedelta, timezone
from pathlib import Path
from typing import Sequence

from predictionio_tpu import faults
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import base


def _ts(dt: datetime) -> float:
    return dt.timestamp()


def _from_ts(ts: float) -> datetime:
    return datetime.fromtimestamp(ts, tz=timezone.utc)


def _is_missing_table(err: sqlite3.OperationalError) -> bool:
    return "no such table" in str(err)


class SQLiteStorageClient:
    """One sqlite database file shared by all DAOs of this source."""

    def __init__(self, config: dict | None = None):
        self.config = config or {}
        path = self.config.get("path", ":memory:")
        if path != ":memory:":
            Path(path).parent.mkdir(parents=True, exist_ok=True)
        self.lock = threading.RLock()
        self.conn = sqlite3.connect(path, check_same_thread=False)
        self.conn.execute("PRAGMA journal_mode=WAL")
        # counts writes total_changes can't see (DROP TABLE in remove());
        # part of the events change_token
        self.ddl_bump = 0
        self._init_meta_tables()

    def query(self, sql: str, params: tuple | list = ()) -> list:
        """Locked read: serialized against writers on the shared connection
        so readers never observe another thread's uncommitted transaction."""
        with self.lock:
            return self.conn.execute(sql, params).fetchall()

    def query_one(self, sql: str, params: tuple | list = ()):
        rows = self.query(sql, params)
        return rows[0] if rows else None

    def _init_meta_tables(self) -> None:
        with self.lock, self.conn:
            self.conn.executescript(
                """
                CREATE TABLE IF NOT EXISTS pio_apps (
                  id INTEGER PRIMARY KEY AUTOINCREMENT,
                  name TEXT NOT NULL UNIQUE,
                  description TEXT);
                CREATE TABLE IF NOT EXISTS pio_access_keys (
                  accesskey TEXT PRIMARY KEY,
                  appid INTEGER NOT NULL,
                  events TEXT NOT NULL);
                CREATE TABLE IF NOT EXISTS pio_channels (
                  id INTEGER PRIMARY KEY AUTOINCREMENT,
                  name TEXT NOT NULL,
                  appid INTEGER NOT NULL,
                  UNIQUE(name, appid));
                CREATE TABLE IF NOT EXISTS pio_engine_instances (
                  id TEXT PRIMARY KEY,
                  status TEXT NOT NULL,
                  starttime REAL NOT NULL,
                  endtime REAL NOT NULL,
                  engineid TEXT NOT NULL,
                  engineversion TEXT NOT NULL,
                  enginevariant TEXT NOT NULL,
                  enginefactory TEXT NOT NULL,
                  batch TEXT,
                  env TEXT,
                  runtimeconf TEXT,
                  datasourceparams TEXT,
                  preparatorparams TEXT,
                  algorithmsparams TEXT,
                  servingparams TEXT);
                CREATE TABLE IF NOT EXISTS pio_evaluation_instances (
                  id TEXT PRIMARY KEY,
                  status TEXT NOT NULL,
                  starttime REAL NOT NULL,
                  endtime REAL NOT NULL,
                  evaluationclass TEXT,
                  engineparamsgeneratorclass TEXT,
                  batch TEXT,
                  env TEXT,
                  runtimeconf TEXT,
                  evaluatorresults TEXT,
                  evaluatorresultshtml TEXT,
                  evaluatorresultsjson TEXT);
                CREATE TABLE IF NOT EXISTS pio_models (
                  id TEXT PRIMARY KEY,
                  models BLOB NOT NULL);
                """
            )

    def close(self) -> None:
        with self.lock:
            self.conn.close()


class SQLiteApps(base.Apps):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, app: base.App) -> int | None:
        with self._c.lock:
            try:
                with self._c.conn:
                    if app.id != 0:
                        cur = self._c.conn.execute(
                            "INSERT INTO pio_apps (id, name, description) VALUES (?,?,?)",
                            (app.id, app.name, app.description),
                        )
                    else:
                        cur = self._c.conn.execute(
                            "INSERT INTO pio_apps (name, description) VALUES (?,?)",
                            (app.name, app.description),
                        )
                    return cur.lastrowid
            except sqlite3.IntegrityError:
                return None

    def get(self, app_id: int) -> base.App | None:
        row = self._c.query_one(
            "SELECT id, name, description FROM pio_apps WHERE id=?", (app_id,)
        )
        return base.App(*row) if row else None

    def get_by_name(self, name: str) -> base.App | None:
        row = self._c.query_one(
            "SELECT id, name, description FROM pio_apps WHERE name=?", (name,)
        )
        return base.App(*row) if row else None

    def get_all(self) -> list[base.App]:
        rows = self._c.query("SELECT id, name, description FROM pio_apps ORDER BY id")
        return [base.App(*r) for r in rows]

    def update(self, app: base.App) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "UPDATE pio_apps SET name=?, description=? WHERE id=?",
                (app.name, app.description, app.id),
            )
            return cur.rowcount > 0

    def delete(self, app_id: int) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute("DELETE FROM pio_apps WHERE id=?", (app_id,))
            return cur.rowcount > 0


class SQLiteAccessKeys(base.AccessKeys):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, access_key: base.AccessKey) -> str | None:
        key = access_key.key or base.generate_access_key()
        with self._c.lock:
            try:
                with self._c.conn:
                    self._c.conn.execute(
                        "INSERT INTO pio_access_keys (accesskey, appid, events) VALUES (?,?,?)",
                        (key, access_key.appid, json.dumps(access_key.events)),
                    )
                return key
            except sqlite3.IntegrityError:
                return None

    def get(self, key: str) -> base.AccessKey | None:
        row = self._c.query_one(
            "SELECT accesskey, appid, events FROM pio_access_keys WHERE accesskey=?",
            (key,),
        )
        return base.AccessKey(row[0], row[1], json.loads(row[2])) if row else None

    def get_all(self) -> list[base.AccessKey]:
        rows = self._c.query("SELECT accesskey, appid, events FROM pio_access_keys")
        return [base.AccessKey(r[0], r[1], json.loads(r[2])) for r in rows]

    def get_by_appid(self, appid: int) -> list[base.AccessKey]:
        rows = self._c.query(
            "SELECT accesskey, appid, events FROM pio_access_keys WHERE appid=?",
            (appid,),
        )
        return [base.AccessKey(r[0], r[1], json.loads(r[2])) for r in rows]

    def update(self, access_key: base.AccessKey) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "UPDATE pio_access_keys SET appid=?, events=? WHERE accesskey=?",
                (access_key.appid, json.dumps(access_key.events), access_key.key),
            )
            return cur.rowcount > 0

    def delete(self, key: str) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "DELETE FROM pio_access_keys WHERE accesskey=?", (key,)
            )
            return cur.rowcount > 0


class SQLiteChannels(base.Channels):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, channel: base.Channel) -> int | None:
        if not base.Channel.is_valid_name(channel.name):
            return None
        with self._c.lock:
            try:
                with self._c.conn:
                    if channel.id != 0:
                        cur = self._c.conn.execute(
                            "INSERT INTO pio_channels (id, name, appid) VALUES (?,?,?)",
                            (channel.id, channel.name, channel.appid),
                        )
                    else:
                        cur = self._c.conn.execute(
                            "INSERT INTO pio_channels (name, appid) VALUES (?,?)",
                            (channel.name, channel.appid),
                        )
                    return cur.lastrowid
            except sqlite3.IntegrityError:
                return None

    def get(self, channel_id: int) -> base.Channel | None:
        row = self._c.query_one(
            "SELECT id, name, appid FROM pio_channels WHERE id=?", (channel_id,)
        )
        return base.Channel(*row) if row else None

    def get_by_appid(self, appid: int) -> list[base.Channel]:
        rows = self._c.query(
            "SELECT id, name, appid FROM pio_channels WHERE appid=?", (appid,)
        )
        return [base.Channel(*r) for r in rows]

    def delete(self, channel_id: int) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "DELETE FROM pio_channels WHERE id=?", (channel_id,)
            )
            return cur.rowcount > 0


class SQLiteEngineInstances(base.EngineInstances):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, instance: base.EngineInstance) -> str:
        instance_id = instance.id or uuid.uuid4().hex
        instance.id = instance_id
        with self._c.lock, self._c.conn:
            self._c.conn.execute(
                "INSERT OR REPLACE INTO pio_engine_instances VALUES "
                "(?,?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
                self._row(instance),
            )
        return instance_id

    @staticmethod
    def _row(i: base.EngineInstance):
        return (
            i.id,
            i.status,
            _ts(i.start_time),
            _ts(i.end_time),
            i.engine_id,
            i.engine_version,
            i.engine_variant,
            i.engine_factory,
            i.batch,
            json.dumps(i.env),
            json.dumps(i.runtime_conf),
            i.datasource_params,
            i.preparator_params,
            i.algorithms_params,
            i.serving_params,
        )

    @staticmethod
    def _parse(row) -> base.EngineInstance:
        return base.EngineInstance(
            id=row[0],
            status=row[1],
            start_time=_from_ts(row[2]),
            end_time=_from_ts(row[3]),
            engine_id=row[4],
            engine_version=row[5],
            engine_variant=row[6],
            engine_factory=row[7],
            batch=row[8] or "",
            env=json.loads(row[9] or "{}"),
            runtime_conf=json.loads(row[10] or "{}"),
            datasource_params=row[11] or "{}",
            preparator_params=row[12] or "{}",
            algorithms_params=row[13] or "[]",
            serving_params=row[14] or "{}",
        )

    def get(self, instance_id: str) -> base.EngineInstance | None:
        row = self._c.query_one(
            "SELECT * FROM pio_engine_instances WHERE id=?", (instance_id,)
        )
        return self._parse(row) if row else None

    def get_all(self) -> list[base.EngineInstance]:
        rows = self._c.query("SELECT * FROM pio_engine_instances")
        return [self._parse(r) for r in rows]

    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> list[base.EngineInstance]:
        rows = self._c.query(
            "SELECT * FROM pio_engine_instances WHERE status=? AND engineid=? "
            "AND engineversion=? AND enginevariant=? ORDER BY starttime DESC",
            (
                base.EngineInstanceStatus.COMPLETED,
                engine_id,
                engine_version,
                engine_variant,
            ),
        )
        return [self._parse(r) for r in rows]

    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> base.EngineInstance | None:
        completed = self.get_completed(engine_id, engine_version, engine_variant)
        return completed[0] if completed else None

    def update(self, instance: base.EngineInstance) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "UPDATE pio_engine_instances SET status=?, starttime=?, endtime=?, "
                "engineid=?, engineversion=?, enginevariant=?, enginefactory=?, "
                "batch=?, env=?, runtimeconf=?, datasourceparams=?, "
                "preparatorparams=?, algorithmsparams=?, servingparams=? WHERE id=?",
                self._row(instance)[1:] + (instance.id,),
            )
            return cur.rowcount > 0

    def delete(self, instance_id: str) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "DELETE FROM pio_engine_instances WHERE id=?", (instance_id,)
            )
            return cur.rowcount > 0


class SQLiteEvaluationInstances(base.EvaluationInstances):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, instance: base.EvaluationInstance) -> str:
        instance_id = instance.id or uuid.uuid4().hex
        instance.id = instance_id
        with self._c.lock, self._c.conn:
            self._c.conn.execute(
                "INSERT OR REPLACE INTO pio_evaluation_instances VALUES "
                "(?,?,?,?,?,?,?,?,?,?,?,?)",
                self._row(instance),
            )
        return instance_id

    @staticmethod
    def _row(i: base.EvaluationInstance):
        return (
            i.id,
            i.status,
            _ts(i.start_time),
            _ts(i.end_time),
            i.evaluation_class,
            i.engine_params_generator_class,
            i.batch,
            json.dumps(i.env),
            json.dumps(i.runtime_conf),
            i.evaluator_results,
            i.evaluator_results_html,
            i.evaluator_results_json,
        )

    @staticmethod
    def _parse(row) -> base.EvaluationInstance:
        return base.EvaluationInstance(
            id=row[0],
            status=row[1],
            start_time=_from_ts(row[2]),
            end_time=_from_ts(row[3]),
            evaluation_class=row[4] or "",
            engine_params_generator_class=row[5] or "",
            batch=row[6] or "",
            env=json.loads(row[7] or "{}"),
            runtime_conf=json.loads(row[8] or "{}"),
            evaluator_results=row[9] or "",
            evaluator_results_html=row[10] or "",
            evaluator_results_json=row[11] or "",
        )

    def get(self, instance_id: str) -> base.EvaluationInstance | None:
        row = self._c.query_one(
            "SELECT * FROM pio_evaluation_instances WHERE id=?", (instance_id,)
        )
        return self._parse(row) if row else None

    def get_all(self) -> list[base.EvaluationInstance]:
        rows = self._c.query("SELECT * FROM pio_evaluation_instances")
        return [self._parse(r) for r in rows]

    def get_completed(self) -> list[base.EvaluationInstance]:
        rows = self._c.query(
            "SELECT * FROM pio_evaluation_instances WHERE status=? "
            "ORDER BY starttime DESC",
            (base.EvaluationInstanceStatus.EVALCOMPLETED,),
        )
        return [self._parse(r) for r in rows]

    def update(self, instance: base.EvaluationInstance) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "UPDATE pio_evaluation_instances SET status=?, starttime=?, "
                "endtime=?, evaluationclass=?, engineparamsgeneratorclass=?, "
                "batch=?, env=?, runtimeconf=?, evaluatorresults=?, "
                "evaluatorresultshtml=?, evaluatorresultsjson=? WHERE id=?",
                self._row(instance)[1:] + (instance.id,),
            )
            return cur.rowcount > 0

    def delete(self, instance_id: str) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "DELETE FROM pio_evaluation_instances WHERE id=?", (instance_id,)
            )
            return cur.rowcount > 0


class SQLiteModels(base.Models):
    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    def insert(self, model: base.Model) -> None:
        with self._c.lock, self._c.conn:
            self._c.conn.execute(
                "INSERT OR REPLACE INTO pio_models (id, models) VALUES (?,?)",
                (model.id, model.models),
            )

    def get(self, model_id: str) -> base.Model | None:
        row = self._c.query_one(
            "SELECT id, models FROM pio_models WHERE id=?", (model_id,)
        )
        return base.Model(row[0], row[1]) if row else None

    def delete(self, model_id: str) -> bool:
        with self._c.lock, self._c.conn:
            cur = self._c.conn.execute(
                "DELETE FROM pio_models WHERE id=?", (model_id,)
            )
            return cur.rowcount > 0


class SQLiteEvents(base.Events):
    """Per-(app, channel) event tables named ``pio_event_<appId>[_<ch>]``
    (reference JDBCLEvents.scala:37)."""

    entity_indexed = True  # (entitytype, entityid) btree index per table

    def __init__(self, client: SQLiteStorageClient):
        self._c = client

    @staticmethod
    def _table(app_id: int, channel_id: int | None) -> str:
        suffix = f"_{channel_id}" if channel_id is not None else ""
        return f"pio_event_{app_id}{suffix}"

    def init(self, app_id: int, channel_id: int | None = None) -> bool:
        t = self._table(app_id, channel_id)
        with self._c.lock, self._c.conn:
            self._c.conn.executescript(
                f"""
                CREATE TABLE IF NOT EXISTS {t} (
                  id TEXT PRIMARY KEY,
                  event TEXT NOT NULL,
                  entitytype TEXT NOT NULL,
                  entityid TEXT NOT NULL,
                  targetentitytype TEXT,
                  targetentityid TEXT,
                  properties TEXT,
                  eventtime REAL NOT NULL,
                  eventtimezone TEXT,
                  tags TEXT,
                  prid TEXT,
                  creationtime REAL NOT NULL);
                CREATE INDEX IF NOT EXISTS {t}_time ON {t} (eventtime);
                CREATE INDEX IF NOT EXISTS {t}_entity ON {t} (entitytype, entityid);
                """
            )
        return True

    def remove(self, app_id: int, channel_id: int | None = None) -> bool:
        t = self._table(app_id, channel_id)
        with self._c.lock, self._c.conn:
            self._c.conn.execute(f"DROP TABLE IF EXISTS {t}")
            # DROP TABLE bumps neither total_changes nor our own
            # connection's data_version; the token must still change
            self._c.ddl_bump += 1
        return True

    def change_token(
        self, app_id: int, channel_id: int | None = None
    ) -> object | None:
        """(data_version, total_changes, ddl_bump): ``PRAGMA
        data_version`` bumps when ANOTHER connection commits,
        ``total_changes`` counts this connection's row writes, and
        ``ddl_bump`` covers this connection's DROP TABLEs (remove()) —
        together any write to the database changes the triple.
        Database-wide, so it may over-invalidate across apps (allowed by
        the contract)."""
        with self._c.lock:
            dv = self._c.conn.execute("PRAGMA data_version").fetchone()[0]
            return (dv, self._c.conn.total_changes, self._c.ddl_bump)

    def tail_end(
        self, app_id: int, channel_id: int | None = None
    ) -> object | None:
        t = self._table(app_id, channel_id)
        try:
            row = self._c.query_one(f"SELECT COALESCE(MAX(rowid), 0) FROM {t}")
        except sqlite3.OperationalError as err:
            if _is_missing_table(err):
                return 0
            raise
        return int(row[0]) if row and row[0] is not None else 0

    def tail_events(
        self,
        app_id: int,
        channel_id: int | None = None,
        after: object | None = None,
        limit: int | None = None,
    ) -> tuple[list[Event], object]:
        """Max-rowid cursor: rowids are monotone for appends, so
        ``rowid > after`` is exactly the events inserted since the
        cursor (INSERT OR REPLACE assigns a fresh rowid — a replaced
        event re-delivers in its new state, deduped by the consumer)."""
        t = self._table(app_id, channel_id)
        cursor = int(after or 0)
        lim = int(limit) if limit is not None and limit > 0 else -1
        try:
            rows = self._c.query(
                f"SELECT rowid, * FROM {t} WHERE rowid > ? "
                f"ORDER BY rowid LIMIT ?",
                (cursor, lim),
            )
        except sqlite3.OperationalError as err:
            if _is_missing_table(err):
                return [], cursor
            raise
        if rows:
            cursor = int(rows[-1][0])
        return [self._parse(r[1:]) for r in rows], cursor

    @staticmethod
    def _tz_offset_seconds(dt: datetime) -> int:
        off = dt.utcoffset()
        return int(off.total_seconds()) if off is not None else 0

    @staticmethod
    def _to_row(event: Event, event_id: str) -> tuple:
        return (
            event_id,
            event.event,
            event.entity_type,
            event.entity_id,
            event.target_entity_type,
            event.target_entity_id,
            event.properties.to_json(),
            _ts(event.event_time),
            str(SQLiteEvents._tz_offset_seconds(event.event_time)),
            json.dumps(list(event.tags)),
            event.pr_id,
            _ts(event.creation_time),
        )

    def insert(self, event: Event, app_id: int, channel_id: int | None = None) -> str:
        return self.batch_insert([event], app_id, channel_id)[0]

    def batch_insert(
        self, events, app_id: int, channel_id: int | None = None
    ) -> list[str]:
        """Contract (base.Events): the namespace is auto-created and
        re-inserting an existing event_id replaces the stored event."""
        t = self._table(app_id, channel_id)
        rows, ids = [], []
        for event in events:
            event_id = event.event_id or uuid.uuid4().hex
            ids.append(event_id)
            rows.append(self._to_row(event, event_id))
        sql = f"INSERT OR REPLACE INTO {t} VALUES (?,?,?,?,?,?,?,?,?,?,?,?)"
        with self._c.lock:
            faults.fault_point("storage.sqlite.commit")
            try:
                with self._c.conn:
                    self._c.conn.executemany(sql, rows)
            except sqlite3.OperationalError as err:
                if not _is_missing_table(err):
                    raise
                self.init(app_id, channel_id)
                with self._c.conn:
                    self._c.conn.executemany(sql, rows)
        return ids

    @staticmethod
    def _parse(row) -> Event:
        try:
            tz = timezone(timedelta(seconds=int(row[8])))
        except (TypeError, ValueError):
            tz = timezone.utc
        return Event(
            event_id=row[0],
            event=row[1],
            entity_type=row[2],
            entity_id=row[3],
            target_entity_type=row[4],
            target_entity_id=row[5],
            properties=DataMap.from_json(row[6] or "{}"),
            event_time=_from_ts(row[7]).astimezone(tz),
            tags=tuple(json.loads(row[9] or "[]")),
            pr_id=row[10],
            creation_time=_from_ts(row[11]),
        )

    def get(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> Event | None:
        t = self._table(app_id, channel_id)
        try:
            row = self._c.query_one(f"SELECT * FROM {t} WHERE id=?", (event_id,))
        except sqlite3.OperationalError as err:
            if _is_missing_table(err):
                return None
            raise
        return self._parse(row) if row else None

    def delete(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> bool:
        t = self._table(app_id, channel_id)
        with self._c.lock, self._c.conn:
            try:
                cur = self._c.conn.execute(f"DELETE FROM {t} WHERE id=?", (event_id,))
            except sqlite3.OperationalError as err:
                if _is_missing_table(err):
                    return False
                raise
            return cur.rowcount > 0

    def find_target_ids(
        self, app_id, channel_id, entity_type, entity_id, event_names,
        target_entity_type,
    ) -> set[str]:
        """One projection over the (entitytype, entityid) index."""
        names = list(event_names)
        if not names:
            return set()
        t = self._table(app_id, channel_id)
        try:
            rows = self._c.query(
                f"SELECT DISTINCT targetentityid FROM {t} WHERE entitytype = ?"
                " AND entityid = ? AND targetentitytype = ?"
                " AND targetentityid IS NOT NULL AND event IN ("
                + ",".join("?" * len(names)) + ")",
                (entity_type, entity_id, target_entity_type, *names),
            )
        except sqlite3.OperationalError as err:
            if _is_missing_table(err):
                return set()
            raise
        return {r[0] for r in rows if r[0]}

    @staticmethod
    def _rating_value_col(rating_key: str) -> tuple[str, list]:
        """(SELECT expression, its bound params) extracting the numeric
        rating from the properties JSON — the SQL-dialect hook the
        postgres backend overrides. JSON booleans extract as integers
        1/0 in sqlite, but the base/jsonl backends reject booleans (fall
        back to the event-name default) — parity requires the same."""
        if '"' in rating_key:
            raise ValueError("rating_key must not contain double quotes")
        path_expr = f"properties, '$.\"{rating_key}\"'"
        return (
            f"CASE WHEN json_type({path_expr}) IN ('integer', 'real') "
            f"THEN json_extract({path_expr}) ELSE NULL END",
            [],
        )

    def scan_ratings(
        self,
        app_id: int,
        channel_id: int | None = None,
        *,
        event_names=None,
        entity_type: str | None = None,
        target_entity_type: str | None = None,
        rating_key: str | None = "rating",
        default_ratings: dict[str, float] | None = None,
        override_ratings: dict[str, float] | None = None,
    ) -> base.RatingsBatch:
        """Columnar fast path: a 4-column SQL projection with json1
        extracting the rating — the DB does the filtering and property
        parse, Python only dense-indexes ids in fetchmany batches; no
        Event objects (reference JDBCPEvents JdbcRDD read,
        storage/jdbc/.../JDBCPEvents.scala:91)."""
        import numpy as np

        t = self._table(app_id, channel_id)
        clauses, params = ["targetentityid IS NOT NULL"], []
        if entity_type is not None:
            clauses.append("entitytype = ?")
            params.append(entity_type)
        if target_entity_type is not None:
            clauses.append("targetentitytype = ?")
            params.append(target_entity_type)
        if event_names is not None:
            event_names = list(event_names)
            if not event_names:
                return base.RatingsBatch.empty()
            clauses.append("event IN (" + ",".join("?" * len(event_names)) + ")")
            params.extend(event_names)
        if rating_key is None:
            value_col, vparams = "NULL", []  # implicit: name defaults only
        else:
            value_col, vparams = self._rating_value_col(rating_key)
        sql = (
            f"SELECT entityid, targetentityid, event, {value_col} "
            f"FROM {t} WHERE " + " AND ".join(clauses)
        )
        params = vparams + params  # value_col placeholders come first
        user_map: dict[str, int] = {}
        item_map: dict[str, int] = {}
        rows: list[int] = []
        cols: list[int] = []
        vals: list[float] = []
        defaults = default_ratings or {}
        forced = override_ratings or {}
        with self._c.lock:
            try:
                cur = self._c.conn.execute(sql, params)
            except sqlite3.OperationalError as err:
                if _is_missing_table(err):
                    cur = None
                else:
                    raise
            while cur is not None:
                batch = cur.fetchmany(65536)
                if not batch:
                    break
                for u, it, ev, v in batch:
                    fv = forced.get(ev)
                    if fv is not None:
                        v = fv
                    elif not isinstance(v, (int, float)) or isinstance(v, bool):
                        v = defaults.get(ev)
                        if v is None:
                            continue
                    rows.append(user_map.setdefault(u, len(user_map)))
                    cols.append(item_map.setdefault(it, len(item_map)))
                    vals.append(float(v))
        return base.RatingsBatch(
            entity_ids=list(user_map),
            target_ids=list(item_map),
            rows=np.asarray(rows, dtype=np.int32),
            cols=np.asarray(cols, dtype=np.int32),
            vals=np.asarray(vals, dtype=np.float32),
        )

    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type=...,
        target_entity_id=...,
        limit: int | None = None,
        reversed_order: bool = False,
    ) -> list[Event]:
        t = self._table(app_id, channel_id)
        clauses, params = [], []
        if start_time is not None:
            clauses.append("eventtime >= ?")
            params.append(_ts(start_time))
        if until_time is not None:
            clauses.append("eventtime < ?")
            params.append(_ts(until_time))
        if entity_type is not None:
            clauses.append("entitytype = ?")
            params.append(entity_type)
        if entity_id is not None:
            clauses.append("entityid = ?")
            params.append(entity_id)
        if event_names is not None:
            if not event_names:
                return []  # empty name filter matches nothing
            clauses.append(
                "event IN (" + ",".join("?" * len(event_names)) + ")"
            )
            params.extend(event_names)
        if target_entity_type is not ...:
            if target_entity_type is None:
                clauses.append("targetentitytype IS NULL")
            else:
                clauses.append("targetentitytype = ?")
                params.append(target_entity_type)
        if target_entity_id is not ...:
            if target_entity_id is None:
                clauses.append("targetentityid IS NULL")
            else:
                clauses.append("targetentityid = ?")
                params.append(target_entity_id)
        where = (" WHERE " + " AND ".join(clauses)) if clauses else ""
        order = "DESC" if reversed_order else "ASC"
        sql = f"SELECT * FROM {t}{where} ORDER BY eventtime {order}"
        if limit is not None and limit >= 0:
            sql += f" LIMIT {int(limit)}"
        try:
            rows = self._c.query(sql, params)
        except sqlite3.OperationalError as err:
            if _is_missing_table(err):
                return []
            raise
        return [self._parse(r) for r in rows]

    def close(self) -> None:
        pass
