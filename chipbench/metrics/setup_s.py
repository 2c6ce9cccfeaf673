"""setup_s: seconds from the process's start to the window's: corpus
generation, index build, compiles or cache loads, and warm-up."""


def read(run):
    return run.setup_s
