"""Time in collective operations over the device's busy time, in
percent. Nothing to read on one chip."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["busy_s"] or not t["collective_s"]:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
