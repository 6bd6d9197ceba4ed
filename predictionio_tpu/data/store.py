"""Engine-facing event store facades.

Capability parity with the reference's stable template-facing API
(data/.../store/PEventStore.scala:35-121, LEventStore.scala:33-145,
Common.scala:24-53): app-*name*-based queries resolved to app/channel ids
through the metadata store. Templates read events through this module only,
never through DAOs directly.

TPU note: ``find`` returns host-side lists; the array builders in
``predictionio_tpu.ops`` convert them to dense/padded device arrays (the
RDD-to-array boundary).
"""

from __future__ import annotations

from datetime import datetime
from typing import Sequence

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import Storage, get_storage


class EventStoreError(RuntimeError):
    pass


def app_name_to_id(
    app_name: str, channel_name: str | None = None, storage: Storage | None = None
) -> tuple[int, int | None]:
    """Resolve (appName, channelName) -> (appId, channelId)
    (reference store/Common.scala:24-53)."""
    storage = storage or get_storage()
    app = storage.get_metadata_apps().get_by_name(app_name)
    if app is None:
        raise EventStoreError(
            f"Invalid app name {app_name}. Please use valid app name."
        )
    if channel_name is None:
        return app.id, None
    for ch in storage.get_metadata_channels().get_by_appid(app.id):
        if ch.name == channel_name:
            return app.id, ch.id
    raise EventStoreError(
        f"Invalid channel name {channel_name} for app {app_name}."
    )


def find(
    app_name: str,
    channel_name: str | None = None,
    start_time: datetime | None = None,
    until_time: datetime | None = None,
    entity_type: str | None = None,
    entity_id: str | None = None,
    event_names: Sequence[str] | None = None,
    target_entity_type=...,
    target_entity_id=...,
    limit: int | None = None,
    reversed_order: bool = False,
    storage: Storage | None = None,
) -> list[Event]:
    """Query events by app name (PEventStore.find / LEventStore.find)."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    return storage.get_events().find(
        app_id=app_id,
        channel_id=channel_id,
        start_time=start_time,
        until_time=until_time,
        entity_type=entity_type,
        entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit,
        reversed_order=reversed_order,
    )


def change_token(
    app_name: str,
    channel_name: str | None = None,
    storage: Storage | None = None,
) -> object | None:
    """Cheap change token for an app's event set (``None`` = backend
    can't provide one; see ``base.Events.change_token``). Serving-time
    caches key on this to skip re-reading a store that hasn't changed."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    return storage.get_events().change_token(app_id, channel_id)


def find_by_entity(
    app_name: str,
    entity_type: str,
    entity_id: str,
    channel_name: str | None = None,
    event_names: Sequence[str] | None = None,
    target_entity_type=...,
    target_entity_id=...,
    start_time: datetime | None = None,
    until_time: datetime | None = None,
    limit: int | None = None,
    latest: bool = True,
    storage: Storage | None = None,
) -> list[Event]:
    """Serving-time point query (LEventStore.findByEntity:33-97) — the path
    e-commerce-style business rules use per request."""
    return find(
        app_name=app_name,
        channel_name=channel_name,
        start_time=start_time,
        until_time=until_time,
        entity_type=entity_type,
        entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit,
        reversed_order=latest,
        storage=storage,
    )


def find_target_ids(
    app_name: str,
    entity_type: str,
    entity_id: str,
    event_names: Sequence[str],
    target_entity_type: str,
    channel_name: str | None = None,
    storage: Storage | None = None,
) -> set[str]:
    """The distinct targets of one entity's events (``Events.
    find_target_ids``): the seen-items rule's serving-time read."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    return storage.get_events().find_target_ids(
        app_id, channel_id, entity_type, entity_id, event_names,
        target_entity_type,
    )


def find_ratings(
    app_name: str,
    channel_name: str | None = None,
    event_names: Sequence[str] | None = None,
    entity_type: str | None = None,
    target_entity_type: str | None = None,
    rating_key: str | None = "rating",
    default_ratings: dict[str, float] | None = None,
    override_ratings: dict[str, float] | None = None,
    storage: Storage | None = None,
):
    """Columnar bulk training read: dense-indexed (rows, cols, vals)
    arrays plus the id lists, WITHOUT materializing per-event Python
    objects — the streaming replacement for ``find`` + per-event loops in
    template DataSources (reference PEvents.find -> RDD pipeline,
    data/.../storage/PEvents.scala:38-188). Returns a
    :class:`predictionio_tpu.data.storage.base.RatingsBatch`.
    """
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    return storage.get_events().scan_ratings(
        app_id,
        channel_id,
        event_names=event_names,
        entity_type=entity_type,
        target_entity_type=target_entity_type,
        rating_key=rating_key,
        default_ratings=default_ratings,
        override_ratings=override_ratings,
    )


def warm_columnar_cache(
    app_name: str,
    channel_name: str | None = None,
    rating_key: str | None = "rating",
    storage: Storage | None = None,
) -> int:
    """Pre-build the columnar segment cache for an app's events so the
    FIRST training read is already the mmap fast path (run after a bulk
    import, before a train — e.g. ``pio import --warm-cache``). A full
    ``scan_ratings`` both proves the logs replay-clean and publishes the
    column blocks as a side effect; backends without the cache
    (``supports_columnar_cache`` False) just do a scan. Returns the
    number of rating rows scanned."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    batch = storage.get_events().scan_ratings(
        app_id, channel_id, rating_key=rating_key
    )
    return len(batch.vals)


def aggregate_properties(
    app_name: str,
    entity_type: str,
    channel_name: str | None = None,
    start_time: datetime | None = None,
    until_time: datetime | None = None,
    required: Sequence[str] | None = None,
    storage: Storage | None = None,
):
    """Aggregated entityId -> PropertyMap (PEventStore.aggregateProperties)."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    return storage.get_events().aggregate_properties(
        app_id=app_id,
        channel_id=channel_id,
        entity_type=entity_type,
        start_time=start_time,
        until_time=until_time,
        required=required,
    )
