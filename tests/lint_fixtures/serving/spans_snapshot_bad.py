"""Fixture: span totals read to make a decision in serving/."""
from repro.util import spans


def pick_tile(tiles):
    spent = spans.snapshot().get("engine.stage", (0, 0.0, 0.0))[1]
    return tiles[0] if spent > 1.0 else tiles[-1]
