"""The fused tick's share of its roofline: the least time the chip could
take for the ticks of the traced window (the larger of their operations
over the peak and their bytes over the HBM bandwidth, counted from the
live, unpadded windows and the outputs the service reads; `flops.py`),
over the device time of the `_fused_tick_impl` module's executions."""

import flops

MODULE = "jit__fused_tick_impl"


def read(run: dict):
    if run.get("driver") != "fleet":
        return None
    device_s = run["trace"].module_s.get(MODULE, 0.0)
    if device_s <= 0.0 or run["tick_bytes"] <= 0.0:
        return None
    least, _ = flops.roofline_seconds(run["tick_flops"], run["tick_bytes"], run["peak"])
    return 100.0 * least / device_s
