"""Memory-bound floor of the device time over the device's busy time.

Bound: HBM bandwidth.  The floor is 4 bytes written for every value of a
32-bit device-routable column that the completed queries must produce
(reference.device_values: each filter column over every row, each other
column read over the matching rows), over the peak HBM bandwidth of
peaks.json.  The busy time is the union of device operations in the
trace.  Nothing here reads the program's counters, so the work counted
is the same whatever does it."""


def read(r):
    t, p = r["trace"], r["peaks"]
    if not t or not t["busy_s"] or not r["required_values"] or not p:
        return None
    floor_s = 4.0 * r["required_values"] / p["hbm_bytes_per_s"]
    return 100.0 * floor_s / t["busy_s"]
