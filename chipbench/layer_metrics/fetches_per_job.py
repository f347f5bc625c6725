"""Device-to-host fetches per job (each is a host sync):
``overall_stats()["device_fetches"]`` over the traced jobs."""


def read(run: dict):
    return run["stats"]["device_fetches"] / run["jobs"] \
        if run["jobs"] else None
