"""Device idle share of the traced window: 1 - (union of device op
intervals / window), in percent."""
from harness.view import idle_pct


def read(run):
    return idle_pct(run)
