"""Server result-cache hits over hits and misses in the window, from the
ServerStats counters (serve/cache.py) read before and after it."""


def read(r):
    s = r["server"]
    if not s or s["result_hits"] + s["result_misses"] == 0:
        return None
    return 100.0 * s["result_hits"] / (s["result_hits"] + s["result_misses"])
