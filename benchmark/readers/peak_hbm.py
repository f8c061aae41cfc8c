"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, in GB
(10^9 bytes), over the whole process (set-up and reference check included)."""


def read(context):
    peak = context["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
