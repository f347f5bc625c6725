"""``peak_bytes_in_use / bytes_limit`` on the fullest device after the
window, in percent."""


def read(run: dict):
    shares = [m["peak_bytes_in_use"] / m["bytes_limit"]
              for m in run["memory"]
              if m.get("peak_bytes_in_use") and m.get("bytes_limit")]
    return 100.0 * max(shares) if shares else None
