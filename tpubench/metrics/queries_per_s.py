"""Closed loop: queries completed over the whole window, which ends at
the first completion after the run's seconds, so no query is cut off."""


def read(r):
    if not r["closed"]:
        return None
    return r["completed"] / r["elapsed_s"]
