"""Largest value of a quantity the harness sampled after every fleet
step of the window, as a percentage if asked."""


def read(obs, sample, percent=False):
    xs = [s[sample] for s in obs["steps"] if s.get(sample) is not None]
    if not xs:
        return None
    return max(xs) * (100.0 if percent else 1.0)
