"""Device: peak bytes in use on the fullest chip, from ``/health``."""


def read(ctx):
    peaks = [d["peak_bytes_in_use"] for d in ctx["health"].get("devices", [])
             if "peak_bytes_in_use" in d]
    return max(peaks) / 2 ** 30 if peaks else None
