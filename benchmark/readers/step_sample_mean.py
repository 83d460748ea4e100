"""Mean of a quantity the harness sampled after every fleet step of the
window, optionally over an engine setting (`over`), as a percentage."""


def read(obs, sample, over=None, percent=False):
    xs = [s[sample] for s in obs["steps"] if s.get(sample) is not None]
    if not xs:
        return None
    v = sum(xs) / len(xs)
    if over is not None:
        v /= obs["engine"][over]
    return v * 100.0 if percent else v
