"""Rows that entered an exchange per job: ``overall_stats()
["xchg_rows_in"]`` over the traced jobs. The program adds the send
matrix's total where it accounts an exchange's traffic
(``data/exchange.py account_traffic``), once per exchange: in
WordCount, what the pre-phase's local fold leaves to shuffle. ``None``
where the counter is absent (a parent commit's program) or no exchange
ran."""


def read(run: dict):
    rows, jobs = run["stats"].get("xchg_rows_in"), run.get("jobs")
    if rows is None or not jobs or not run["stats"].get("exchanges"):
        return None
    return rows / jobs
