"""Share of decoded pages that the jax backend's 32-bit gate sent to the
host over the window, decode families only (not filter or minmax calls).
From the backend's own counters (core/backend.py)."""


def read(r):
    fam = r["decode_families"]
    host = sum(v for k, v in r["host_pages"].items() if k in fam)
    dev = sum(v for k, v in r["device_pages"].items() if k in fam)
    if host + dev == 0:
        return None
    return 100.0 * host / (host + dev)
