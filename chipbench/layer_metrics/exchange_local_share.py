"""Rows whose destination was their own worker, in percent of the rows
that entered an exchange: ``overall_stats()["xchg_rows_local"]`` (the
trace of the send matrix) over ``["xchg_rows_in"]`` (its total), both
added where the program accounts an exchange's traffic
(``data/exchange.py account_traffic``). A hash partition over W
workers keeps 100 / W % local; duplicate detection's registers keep
more where words are held by one worker alone. ``None`` where either
counter is absent (a parent commit's program) or no row entered an
exchange."""


def read(run: dict):
    local = run["stats"].get("xchg_rows_local")
    rows = run["stats"].get("xchg_rows_in")
    if local is None or not rows:
        return None
    return 100.0 * local / rows
