"""The one adapter onto the engine's private state.

`Engine` records an inter-token gap per emitted token on the host clock at
token readback, appended in emission order to `Engine._lat["itl_s"]`, and
keeps the request each batch slot serves in `Engine._slots`.  The engine
has no public accessor for either; this module is the only place that reads
them, and it fails loudly if the gaps are not there.
"""
from __future__ import annotations


def _itl(engine) -> list:
    lat = getattr(engine, "_lat", None)
    if not isinstance(lat, dict) or not isinstance(lat.get("itl_s"), list):
        raise RuntimeError("the engine records no inter-token gaps "
                           "(Engine._lat['itl_s'] is missing): the ITL "
                           "adapter in benchmarks/chip needs updating")
    return lat["itl_s"]


def itl_mark(engine) -> int:
    """How many gaps the engine has recorded so far."""
    return len(_itl(engine))


def itl_between(engine, start: int, stop: int) -> list[float]:
    """Gaps (seconds) recorded between two marks."""
    return list(_itl(engine)[start:stop])


def observe_slots(engine, slot_of: dict) -> None:
    """Record uid -> slot for every request the engine serves right now
    (nothing before the serve loop has made its slots)."""
    for i, r in enumerate(list(getattr(engine, "_slots", None) or ())):
        if r is not None:
            slot_of.setdefault(r.uid, i)
