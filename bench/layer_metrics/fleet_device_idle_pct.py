"""Share of the traced fleet window in which no operation ran on the
device: 1 minus the union of the `XLA Ops` intervals over the window,
averaged over the cell's chips."""


def read(run: dict):
    if run.get("driver") != "fleet":
        return None
    return 100.0 * run["trace"].idle_share
