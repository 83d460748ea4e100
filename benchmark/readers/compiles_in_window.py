"""Programs JAX lowered inside the measured window (a compile, or a
persistent-cache hit, starts with one). Should be 0."""


def read(obs):
    return obs.get("lowered_in_window")
