"""Process start to the first timed job: runtime start, input
generation, compiling or loading every program, one warm job per input."""


def read(run):
    return run.setup_s
