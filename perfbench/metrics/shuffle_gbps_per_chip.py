"""All input bytes of all jobs in the window / the whole window / chips."""


def read(run):
    return run.window.gbps_per_chip
