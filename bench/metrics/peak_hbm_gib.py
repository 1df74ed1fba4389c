"""Peak device memory, GiB: the larger of the runtime's
`peak_bytes_in_use` after the window and the compiled step's
argument + output - alias + temp bytes, which the runtime's counter
misses on TPU."""

GIB = float(1 << 30)


def read(run):
    c = run.compiled
    compiled = c["argument"] + c["output"] - c["alias"] + c["temp"]
    return max(run.peak_bytes_in_use, compiled) / GIB
