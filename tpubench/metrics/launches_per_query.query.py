"""Programs launched on the device in the traced window per query
completed there (the trace's XLA Modules line)."""


def read(r):
    t = r["trace"]
    done = r.get("completed")
    if not t or not done:
        return None
    return t["launches"] / done
