"""Pulls per job: ``overall_stats()["pulls"]`` over the window's jobs.
The program counts a pull where a root ``stage`` span opens
(``api/dia_base.py stage_span``): whenever an action, or a loop, sends
the pull recursion and the fusion planner off. Once in a job of one
action; once per round and twice more in a job of prefix-doubling
rounds, each of which ends in a read that decides whether another
follows. ``None``, never 0: where the counter is absent (a parent
commit's program) and where the window's jobs pull once each, which
says nothing."""


def read(run: dict):
    pulls, jobs = run["stats"].get("pulls"), run.get("jobs")
    if pulls is None or not jobs or pulls <= jobs:
        return None
    return pulls / jobs
