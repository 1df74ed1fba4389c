"""Temporary bytes of the compiled step (`memory_analysis().temp`), GiB."""

GIB = float(1 << 30)


def read(run):
    return run.compiled["temp"] / GIB
