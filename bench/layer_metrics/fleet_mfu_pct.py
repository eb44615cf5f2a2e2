"""The fleet's whole traced window as a share of the chip's peak: the
operations of every fused tick in it (`flops.tick_flops`, live unpadded
windows), over the peak times the window's length on each chip.  It
bounds `tick_roofline` from the whole run's side: a kernel taken off the
path leaves that roofline silent, and this share still reads."""


def read(run: dict):
    if run.get("driver") != "fleet" or run["tick_flops"] <= 0.0:
        return None
    return 100.0 * run["tick_flops"] / (
        run["chips"] * run["peak"]["flops_per_s"] * run["trace"].window_s)
