"""Bytes the exchange moved per job: ``overall_stats()["bytes_moved"]``
over the traced jobs."""


def read(run: dict):
    if not run["jobs"] or not run["stats"]["exchanges"]:
        return None
    return run["stats"]["bytes_moved"] / run["jobs"]
