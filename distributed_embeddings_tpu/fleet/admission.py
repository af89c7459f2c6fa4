"""Admission control for the serving fleet (ISSUE 16).

The serve path NEVER raises for load reasons: `FleetRouter.submit`
returns a typed `RouteResult`, and a shed is a value the caller can
count, retry elsewhere, or degrade on — not an exception unwinding an
RPC handler mid-traffic. The policy sheds *before* p99 explodes: the
signals are the per-replica `MicroBatcher` instruments that already
exist (`queue_depth`, `queued_rows`), read at submit time, so a replica
drowning in queued work stops accepting more instead of serving every
request late.

Shedding (not spilling to a sibling) is deliberate: a spilled request
would land on a replica whose cache never sees that key range — it
would be served, slowly, while polluting the sibling's cache. Capacity
comes from adding replicas (elastic membership), not from breaking key
affinity under pressure.
"""

import os
from typing import Optional

__all__ = ["RouteResult", "AdmissionController"]


class RouteResult:
    """Typed outcome of one `FleetRouter.submit`.

    ``accepted=True``: `replica` took the request, `handle` resolves in
    the next `FleetRouter.flush()`. ``accepted=False``: the request was
    shed — `shed_reason` says why (``queue_depth`` / ``queue_rows`` /
    ``no_replicas`` / ``oversize`` / ``router_error``) and `replica`
    names the overloaded target when one was resolved."""

    __slots__ = ("accepted", "replica", "handle", "shed_reason", "key")

    def __init__(self, accepted: bool, replica: Optional[str] = None,
                 handle: Optional[int] = None,
                 shed_reason: Optional[str] = None, key=None):
        self.accepted = bool(accepted)
        self.replica = replica
        self.handle = handle
        self.shed_reason = shed_reason
        self.key = key

    def __bool__(self) -> bool:
        return self.accepted

    def __repr__(self) -> str:
        if self.accepted:
            return (f"RouteResult(accepted, replica={self.replica!r}, "
                    f"handle={self.handle})")
        return (f"RouteResult(shed, reason={self.shed_reason!r}, "
                f"replica={self.replica!r})")


class AdmissionController:
    """Shed decision over one replica's batcher instruments.

    Args:
      max_queue_depth: shed when the target batcher already holds this
        many queued requests (default: ``DET_FLEET_MAX_QUEUE_DEPTH``
        env, else 64).
      max_queue_rows: optional row-level cap — shed when accepting the
        request would push the batcher's queued true rows past it
        (default: ``DET_FLEET_MAX_QUEUE_ROWS`` env, else unlimited).
    """

    def __init__(self, max_queue_depth: Optional[int] = None,
                 max_queue_rows: Optional[int] = None):
        if max_queue_depth is None:
            max_queue_depth = int(os.environ.get(
                "DET_FLEET_MAX_QUEUE_DEPTH", "64"))
        if max_queue_rows is None:
            raw = os.environ.get("DET_FLEET_MAX_QUEUE_ROWS", "")
            max_queue_rows = int(raw) if raw else None
        self.max_queue_depth = int(max_queue_depth)
        self.max_queue_rows = (None if max_queue_rows is None
                               else int(max_queue_rows))

    def shed_reason(self, batcher, rows: int) -> Optional[str]:
        """None = admit; otherwise the typed shed reason. Reads only
        host-side queue state — never touches the device."""
        if batcher.queue_depth >= self.max_queue_depth:
            return "queue_depth"
        if self.max_queue_rows is not None \
                and batcher.queued_rows + int(rows) > self.max_queue_rows:
            return "queue_rows"
        return None
