"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest device,
read after the window."""


def read(facts, trace):
    if not facts["memory_peak_bytes"]:
        return None
    return facts["memory_peak_bytes"] / 2.0 ** 30
