"""The scan description the workload compiler reads."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple


@dataclass
class ScanSpec:
    """Static description of a range scan: what data it will consume."""

    table: str
    columns: Tuple[str, ...]
    ranges: Tuple[Tuple[int, int], ...]  # half-open tuple ranges, sorted
    tuple_rate: float = 50e6             # tuples/sec of CPU processing
    stream: int = 0
    in_order_required: bool = False

    @property
    def total_tuples(self) -> int:
        return sum(b - a for a, b in self.ranges)
