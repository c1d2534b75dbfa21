"""setup_s: seconds from process start to the window opening (weights,
compiles or cache loads, warm-up of every pool shape, lead-in)."""


def read(run):
    return run.setup_s
