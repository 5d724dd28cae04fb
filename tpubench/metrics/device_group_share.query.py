"""Share of the rows of grouped aggregates that the decode backend
reduced on the device over the window: its ``group_rows`` counters
(core/backend.py), device over device and host."""


def read(r):
    dev = r["device_pages"].get("group_rows", 0)
    host = r["host_pages"].get("group_rows", 0)
    if dev + host == 0:
        return None
    return 100.0 * dev / (dev + host)
