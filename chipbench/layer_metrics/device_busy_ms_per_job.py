"""Device busy time per traced job, the mean over the cell's devices."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["jobs"]:
        return None
    return 1e3 * t["busy_s"] / t["jobs"]
