"""Host time the fleet service charges to decoding and folding one
window: the sums of its own `tick.decode` and `tick.regimes` phase
histograms (`repro.obs`) between the window's first and last tick, per
window submitted between them."""


def read(run: dict):
    if run.get("driver") != "fleet" or not run.get("ingest_windows"):
        return None
    return 1e6 * run["ingest_s"] / run["ingest_windows"]
