"""Process start to the first timed wave: index restore (or build),
compile-cache loads, traffic generation and warm-up."""


def read(rec, trace):
    return rec["setup_s"]
