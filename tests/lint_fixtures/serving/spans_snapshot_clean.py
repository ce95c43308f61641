"""Fixture: clean twin — spans mark the work; no decision reads them."""
from repro.util.spans import span


def pick_tile(tiles, cost_ms):
    with span("engine.pick"):
        return tiles[0] if cost_ms > 1.0 else tiles[-1]
