"""The array policies' names and stable ids, as the simulator's traced
configs carry them (``lru`` 0, ``pbm`` 1, ``cscan`` 2, ``opt`` 3), in the
order the program's policy registry lists them."""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional


class Entry(NamedTuple):
    name: str
    array_id: int
    factory: str


_ENTRIES = (
    Entry("lru", 0, "ArrayLRU"),
    Entry("cscan", 2, "ArrayCScan"),
    Entry("pbm", 1, "ArrayPBM"),
    Entry("opt", 3, "ArrayOPT"),
)
_BY_NAME = {e.name: e for e in _ENTRIES}


def names(backend: Optional[str] = None) -> List[str]:
    return [e.name for e in _ENTRIES]


def get(name: str) -> Entry:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(f"unknown policy {name!r}; known: {names()}") from None


def array_policy(name: str):
    from . import policies

    return getattr(policies, get(name).factory)()


def array_ids() -> Dict[str, int]:
    return {e.name: e.array_id for e in _ENTRIES}


def array_name(array_id: int) -> Optional[str]:
    for e in _ENTRIES:
        if e.array_id == array_id:
            return e.name
    return None
