"""Device dispatches per job: ``overall_stats()["device_dispatches"]``
over the traced jobs."""


def read(run: dict):
    return run["stats"]["device_dispatches"] / run["jobs"] \
        if run["jobs"] else None
