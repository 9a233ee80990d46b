"""Share of the window's scoring sweeps that the program's backend
dispatch sent to the device, in %, from its dispatch log."""

DEVICE_BACKENDS = ("jax", "pallas")


def read(run: dict):
    sweeps = [backend for t in run["tallies"] for backend, _ in t["sweeps"]]
    if not sweeps:
        return None
    return 100.0 * sum(b in DEVICE_BACKENDS for b in sweeps) / len(sweeps)
