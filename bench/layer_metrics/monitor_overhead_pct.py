"""The Monitor's window-close path (gather, label, emit) as a share of the
window's wall time, by the Monitor's own clock
(`Monitor.monitor_path_seconds`).  It leaves out the per-stage recorder."""


def read(run: dict):
    if "monitor_overhead_fraction" not in run:
        return None
    return 100.0 * run["monitor_overhead_fraction"]
