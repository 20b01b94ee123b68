"""Peak device memory in use over the run, on the fullest chip, in GB
(10^9 bytes), as the device's allocator reports it."""


def read(run):
    b = run["memory_peak_bytes"]
    return b / 1e9 if b else None
