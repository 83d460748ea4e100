"""Percentile `q` of how late the generator submitted (submit - due)."""
from benchmark import stats


def read(obs, q):
    xs = stats.lateness(obs["requests"])
    return stats.percentile(xs, q) if xs else None
