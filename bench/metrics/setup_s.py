"""setup_s: process start to the first timed operation, compile included
(host clock)."""


def read(obs):
    return obs["setup_s"]
