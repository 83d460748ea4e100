"""Requests the engines preempted inside the window
(`engine.num_preemptions`, after minus before)."""


def read(obs):
    return obs.get("preemptions_in_window")
