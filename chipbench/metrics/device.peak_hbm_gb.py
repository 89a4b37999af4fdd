"""The most of one device's memory taken at once, on the fullest device,
read after the window and before the reference runs: live arrays plus what
the runtime reserves for the programs' temporaries (run.peak_bytes)."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
