"""Every step of the OOM ladder taken during the window."""

KEYS = ("oom_retries", "segment_splits", "host_fallbacks",
        "admission_spills", "hbm_spills")


def read(run: dict):
    return sum(run["stats"][k] for k in KEYS)
