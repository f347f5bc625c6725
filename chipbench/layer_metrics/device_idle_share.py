"""1 - (union of the device's operation intervals / traced window), the
mean over the cell's devices, in percent."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
