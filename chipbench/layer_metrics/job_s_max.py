"""The longest of the traced jobs on the harness's own clock, upload
included (``run["job_seconds"]``: each job from just before its input
is handed over to its result ready). Per layer and without a bound: a
maximum holds whatever the host lost in one job (``PERF.md`` section 2)."""


def read(run: dict):
    seconds = run.get("job_seconds")
    return max(seconds) if seconds else None
