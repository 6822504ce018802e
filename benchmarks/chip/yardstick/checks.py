"""A number compared with its limit."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Check:
    """``ok`` when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit
