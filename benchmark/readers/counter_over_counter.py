"""The window's growth of one telemetry counter over that of another,
each summed over all of its series: passes a token. None where either
did not grow."""
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def read(obs, numerator, denominator, scale=1.0):
    top = sum(counter_deltas(obs, numerator).values())
    bottom = sum(counter_deltas(obs, denominator).values())
    return top / bottom * scale if top > 0 and bottom > 0 else None
