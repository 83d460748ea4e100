"""Percentile `q` of the time from a request's due moment to its first
token, over the window's counted requests; a failed request is a miss."""
from benchmark import stats


def read(obs, q):
    xs = stats.ttfts(obs["requests"])
    return stats.finite(stats.percentile(xs, q)) if xs else None
