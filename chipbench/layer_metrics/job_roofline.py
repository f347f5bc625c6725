"""The least time a chip could take for a job over the time it was busy.

Least time: the bytes a job must move whatever implements it (the job
kind's ``min_bytes``: input read once, output written once), this
chip's share of them, over the HBM peak of ``peaks.json``. Bound by
bandwidth by definition; a sort cannot reach 100 % of it."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["jobs"] or not t["busy_s"] \
            or run["min_bytes"] is None:
        return None
    least_s = run["min_bytes"] / run["cell"]["chips"] \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / t["jobs"])
