"""Storage DAO contracts and metadata records.

Capability parity with the reference storage abstraction
(data/.../storage/: Apps.scala:32, AccessKeys.scala:35, Channels.scala:32,
EngineInstances.scala:46, EvaluationInstances.scala:42, Models.scala:33,
LEvents.scala:40, PEvents.scala:38). The L/P DAO split collapses here: one
``Events`` contract serves both the serving-time point lookups (L) and the
training-time bulk scans (P); bulk reads return plain lists that feed the
jax/numpy array builders (the RDD analog).
"""

from __future__ import annotations

import abc
import base64
import re
import secrets
from dataclasses import dataclass, field
from datetime import datetime
from typing import Any, Iterable, Sequence

from predictionio_tpu.data.event import Event

# --------------------------------------------------------------------------
# Metadata records
# --------------------------------------------------------------------------


@dataclass
class App:
    """An application namespace for events (reference Apps.scala:32-44)."""

    id: int
    name: str
    description: str | None = None


@dataclass
class AccessKey:
    """Event-server credential, scoped to an app and optionally to specific
    event names (reference AccessKeys.scala:35-50)."""

    key: str
    appid: int
    events: list[str] = field(default_factory=list)


def generate_access_key() -> str:
    """64 random bytes, URL-safe base64 (reference AccessKeys.generateKey).

    Keys never start with ``-`` so they stay safe to pass as positional CLI
    arguments (argparse would treat a leading dash as a flag).
    """
    while True:
        key = base64.urlsafe_b64encode(secrets.token_bytes(48)).decode("ascii").rstrip("=")
        if not key.startswith("-"):
            return key


CHANNEL_NAME_RE = re.compile(r"^[a-zA-Z0-9-]{1,16}$")


@dataclass
class Channel:
    """A named sub-stream of an app's events (reference Channels.scala:32-45).

    Name constraint mirrors Channels.isValidName (1-16 alphanumeric or '-').
    """

    id: int
    name: str
    appid: int

    @staticmethod
    def is_valid_name(name: str) -> bool:
        return bool(CHANNEL_NAME_RE.match(name))


class EngineInstanceStatus:
    INIT = "INIT"
    TRAINING = "TRAINING"
    COMPLETED = "COMPLETED"
    FAILED = "FAILED"


@dataclass
class EngineInstance:
    """One training run's metadata (reference EngineInstances.scala:46-97).

    ``runtime_conf`` is the analog of the reference's ``sparkConf``:
    free-form execution-substrate configuration (mesh shape, precision,
    donation flags) recorded with the run.
    """

    id: str
    status: str
    start_time: datetime
    end_time: datetime
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    batch: str = ""
    env: dict[str, str] = field(default_factory=dict)
    runtime_conf: dict[str, str] = field(default_factory=dict)
    datasource_params: str = "{}"
    preparator_params: str = "{}"
    algorithms_params: str = "[]"
    serving_params: str = "{}"


class EvaluationInstanceStatus:
    INIT = "INIT"
    EVALUATING = "EVALUATING"
    EVALCOMPLETED = "EVALCOMPLETED"
    FAILED = "FAILED"


@dataclass
class EvaluationInstance:
    """One evaluation run's metadata (reference EvaluationInstances.scala:42-81)."""

    id: str
    status: str
    start_time: datetime
    end_time: datetime
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: dict[str, str] = field(default_factory=dict)
    runtime_conf: dict[str, str] = field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""


@dataclass
class Model:
    """A serialized trained model blob (reference Models.scala:33-51)."""

    id: str
    models: bytes


# --------------------------------------------------------------------------
# DAO contracts
# --------------------------------------------------------------------------


class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> int | None:
        """Insert; app.id == 0 means auto-assign. Returns the assigned id."""

    @abc.abstractmethod
    def get(self, app_id: int) -> App | None: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> App | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, access_key: AccessKey) -> str | None:
        """Insert; empty key means generate one. Returns the key."""

    @abc.abstractmethod
    def get(self, key: str) -> AccessKey | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[AccessKey]: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> list[AccessKey]: ...

    @abc.abstractmethod
    def update(self, access_key: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...


class Channels(abc.ABC):
    @abc.abstractmethod
    def insert(self, channel: Channel) -> int | None:
        """Insert; channel.id == 0 means auto-assign. Returns the id."""

    @abc.abstractmethod
    def get(self, channel_id: int) -> Channel | None: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> list[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EngineInstance) -> str:
        """Insert; empty id means auto-assign. Returns the id."""

    @abc.abstractmethod
    def get(self, instance_id: str) -> EngineInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> EngineInstance | None:
        """Most recent COMPLETED instance for (engineId, version, variant) —
        what ``deploy`` picks (reference commands/Engine.scala:224-230)."""

    @abc.abstractmethod
    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> list[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EngineInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> EvaluationInstance | None: ...

    @abc.abstractmethod
    def get_all(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> list[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EvaluationInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class Models(abc.ABC):
    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @abc.abstractmethod
    def get(self, model_id: str) -> Model | None: ...

    @abc.abstractmethod
    def delete(self, model_id: str) -> bool: ...

    def local_path(self, model_id: str) -> str | None:
        """Filesystem path of the stored blob when the backend keeps it
        as a plain local file (localfs), else None. The deploy path uses
        this to mmap model files in place instead of copying the bytes
        through :meth:`get`."""
        return None


@dataclass
class RatingsBatch:
    """Columnar (entity, target, value) training triples with dense ids.

    ``entity_ids[rows[i]] -> target_ids[cols[i]]`` carries ``vals[i]``;
    the id lists double as the BiMap (dense index = list position).
    """

    entity_ids: list[str]
    target_ids: list[str]
    rows: "Any"  # np.ndarray [N] int32
    cols: "Any"  # np.ndarray [N] int32
    vals: "Any"  # np.ndarray [N] float32

    def __len__(self) -> int:
        return len(self.vals)

    def iter_pairs(self):
        """Yield (entity_id, target_id) per record — convenience for
        small-scale consumers; bulk paths should use the arrays."""
        for r, c in zip(self.rows, self.cols):
            yield self.entity_ids[r], self.target_ids[c]

    @staticmethod
    def empty() -> "RatingsBatch":
        import numpy as np

        return RatingsBatch(
            [], [],
            np.empty(0, np.int32), np.empty(0, np.int32),
            np.empty(0, np.float32),
        )


class Events(abc.ABC):
    """Event CRUD + queries for one storage backend.

    Unified L+P contract (reference LEvents.scala:40-513, PEvents.scala:38-188):
    point ops serve the event server and serving-time business rules; ``find``
    with no limit is the bulk training read whose result feeds array builders.
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: int | None = None) -> bool:
        """Create the backing table/namespace for an (app, channel)."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: int | None = None) -> bool:
        """Drop all events of an (app, channel)."""

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int, channel_id: int | None = None) -> str:
        """Insert one event, returning its assigned event id.

        Contract (all backends): the (app, channel) namespace is auto-created
        on first insert, and inserting with an existing ``event_id`` replaces
        the stored event."""

    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> Event | None: ...

    @abc.abstractmethod
    def delete(
        self, event_id: str, app_id: int, channel_id: int | None = None
    ) -> bool: ...

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: int | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type: str | None | type(...) = ...,
        target_entity_id: str | None | type(...) = ...,
        limit: int | None = None,
        reversed_order: bool = False,
    ) -> list[Event]:
        """Query events. ``target_entity_type``/``target_entity_id`` use
        ``...`` (Ellipsis) for "don't care", ``None`` for "must be absent"
        — mirroring the reference's Option[Option[String]] semantics
        (LEvents.scala:282-313). ``limit=None`` or ``-1`` means all."""

    def find_target_ids(
        self,
        app_id: int,
        channel_id: int | None,
        entity_type: str,
        entity_id: str,
        event_names: Sequence[str],
        target_entity_type: str,
    ) -> set[str]:
        """The distinct targets of one entity's events — all a "has this
        user seen this item" rule needs. The default reads the events
        through ``find``; an indexed backend answers with a projection
        and builds no Event objects."""
        return {
            e.target_entity_id
            for e in self.find(
                app_id=app_id, channel_id=channel_id, entity_type=entity_type,
                entity_id=entity_id, event_names=event_names,
                target_entity_type=target_entity_type,
            )
            if e.target_entity_id
        }

    def batch_insert(
        self, events: Iterable[Event], app_id: int, channel_id: int | None = None
    ) -> list[str]:
        return [self.insert(e, app_id, channel_id) for e in events]

    # True when find(entity_id=...) is served by an index (SQL btree,
    # server-side filter) rather than a full replay+filter. Serving-time
    # caches use this to choose between per-entity reads (indexed) and
    # one bulk scan that amortizes across entities (replay backends,
    # where a filtered read costs a full replay anyway).
    entity_indexed = False

    # True when scan_ratings can serve warm reads from a persisted
    # columnar segment cache (see storage/columnar_cache.py). Tooling
    # like store.warm_columnar_cache keys on this to decide whether a
    # priming scan buys anything; the default row-walk below remains
    # the correctness oracle either way.
    supports_columnar_cache = False

    def tail_events(
        self,
        app_id: int,
        channel_id: int | None = None,
        after: object | None = None,
        limit: int | None = None,
    ) -> tuple[list[Event], object] | None:
        """Incremental seq-ordered tail: events appended after cursor
        ``after`` in a backend-defined total order, plus the new cursor.

        ``None`` (the default) means the backend has no cheap seq-ordered
        tail — file-log backends expose :meth:`tail_files` byte offsets
        instead, and the realtime tailer falls back to
        ``change_token``-gated full reads for anything else. ``after=None``
        starts from the beginning of the stream. The cursor is opaque to
        callers (compare/persist only); a backend MAY re-deliver events at
        the cursor boundary (e.g. a timestamp-ordered tail with ties) —
        consumers must dedupe by ``event_id``.
        """
        return None

    def tail_end(
        self, app_id: int, channel_id: int | None = None
    ) -> object | None:
        """Current end-of-stream cursor for :meth:`tail_events` (what a
        tailer resets to when it wants "only events from now on"), or
        ``None`` when the backend has no seq-ordered tail."""
        return None

    def change_token(
        self, app_id: int, channel_id: int | None = None
    ) -> object | None:
        """Cheap opaque token that changes whenever this (app, channel)'s
        event set may have changed; compare tokens with ``!=`` only.

        ``None`` means the backend cannot provide one cheaply — callers
        must then re-read instead of caching. Serving-time business-rule
        caches (the e-commerce template's live seen/unavailable filters)
        key on this so a static store serves from memory while any write
        — including cross-process ones, for file/sqlite backends — is
        seen immediately. Tokens may over-invalidate (e.g. one app's
        write bumping another's token); they must never under-invalidate.
        """
        return None

    def scan_ratings(
        self,
        app_id: int,
        channel_id: int | None = None,
        *,
        event_names: Sequence[str] | None = None,
        entity_type: str | None = None,
        target_entity_type: str | None = None,
        rating_key: "str | None" = "rating",
        default_ratings: "dict[str, float] | None" = None,
        override_ratings: "dict[str, float] | None" = None,
    ) -> "RatingsBatch":
        """Columnar bulk read for (entity -> target, value) training data.

        The streaming analog of the reference's PEvents.find -> RDD ->
        BiMap.stringInt pipeline (PEvents.scala:38-188, BiMap.scala:96-110):
        returns dense-indexed arrays directly so training at event-store
        scale never materializes one Python Event per record. Backends
        override this with a columnar fast path (jsonl: native byte scan;
        sqlite: SQL projection + json1 extraction); this default walks
        ``find`` and is the correctness fallback for small stores.

        ``default_ratings`` maps event names to implicit values used when
        the ``rating_key`` property is absent; ``override_ratings`` maps
        event names to FORCED values that beat any property (the
        reference's ``case "buy" => 4.0`` ignores properties for buy
        events — DataSource.scala:55). ``rating_key=None`` skips property
        extraction entirely — pure implicit feedback, every matching
        event takes its event-name default (view-count style reads).
        """
        user_map: dict[str, int] = {}
        item_map: dict[str, int] = {}
        rows: list[int] = []
        cols: list[int] = []
        vals: list[float] = []
        for e in self.find(
            app_id,
            channel_id,
            entity_type=entity_type,
            event_names=list(event_names) if event_names is not None else None,
            target_entity_type=(
                target_entity_type if target_entity_type is not None else ...
            ),
        ):
            if e.target_entity_id is None:
                continue
            v = (override_ratings or {}).get(e.event)
            if v is None:
                v = (
                    e.properties.to_dict().get(rating_key)
                    if rating_key is not None
                    else None
                )
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    v = (default_ratings or {}).get(e.event)
            if v is None:
                continue
            rows.append(user_map.setdefault(e.entity_id, len(user_map)))
            cols.append(item_map.setdefault(e.target_entity_id, len(item_map)))
            vals.append(float(v))
        import numpy as np

        return RatingsBatch(
            entity_ids=list(user_map),
            target_ids=list(item_map),
            rows=np.asarray(rows, dtype=np.int32),
            cols=np.asarray(cols, dtype=np.int32),
            vals=np.asarray(vals, dtype=np.float32),
        )

    def aggregate_properties(
        self,
        app_id: int,
        channel_id: int | None = None,
        entity_type: str = "",
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        required: Sequence[str] | None = None,
    ) -> dict[str, Any]:
        """Aggregated entityId -> PropertyMap view (LEvents.scala:373-418).

        ``entity_type`` is mandatory (as in the reference API): aggregating
        across entity types would merge unrelated entities sharing an id.
        """
        if not entity_type:
            raise ValueError("aggregate_properties requires entity_type")
        from predictionio_tpu.data.aggregator import (
            AGGREGATOR_EVENT_NAMES,
            aggregate_properties,
        )

        events = self.find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=list(AGGREGATOR_EVENT_NAMES),
        )
        result = aggregate_properties(events)
        if required:
            req = set(required)
            result = {k: v for k, v in result.items() if req.issubset(v.keyset())}
        return result

    def close(self) -> None:
        """Release backend resources."""
