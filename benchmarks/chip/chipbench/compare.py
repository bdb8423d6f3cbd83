"""The comparison of the numbers that decide ``correct`` with their limits."""
from __future__ import annotations

from typing import Dict, Tuple


def judge(numbers: Dict[str, float], limits: Dict[str, dict]) -> Tuple[bool, dict]:
    """Every number against its limit. A number that is missing, not finite
    or above its limit fails."""
    checks, ok = {}, True
    for name, spec in limits.items():
        value = numbers.get(name)
        limit = spec["limit"]
        good = value is not None and value == value and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
