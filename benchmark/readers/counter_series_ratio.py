"""The window's growth of some series of a labelled telemetry counter
over that of others of the SAME counter: pages reclaimed over pages
allocated of one page group. `numerator` and `denominator` list label
strings as the snapshot spells them. None where the denominator did not
grow (a program without the counter included)."""
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def read(obs, counter, numerator, denominator, scale=1.0):
    d = counter_deltas(obs, counter)
    bottom = sum(d.get(k, 0.0) for k in denominator)
    if bottom <= 0:
        return None
    return sum(d.get(k, 0.0) for k in numerator) / bottom * scale
