"""Device time of the block-sparse attention kernels (forward and both
backward sweeps) over the devices' busy time."""


def read(run):
    if run.trace is None:
        return None
    devs = run.trace["devices"].values()
    k = sum(d["kernels"].get("attention", (0.0, 0))[0] for d in devs)
    busy = sum(d["busy_s"] for d in devs)
    return 100.0 * k / busy if k > 0 and busy > 0 else None
