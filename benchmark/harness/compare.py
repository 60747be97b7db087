"""The comparison that decides ``correct``."""

from __future__ import annotations


def mismatch(want: dict, have: dict) -> int:
    """Pods placed on another node than the reference's, or placed by
    one side only (``uid -> node`` on both sides)."""
    return sum(have.get(u) != n for u, n in want.items()) + sum(
        u not in want for u in have
    )
