"""1 - the union of device-operation intervals over the traced window,
the mean over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    devs = run.trace["devices"].values()
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / run.trace["window_s"])
