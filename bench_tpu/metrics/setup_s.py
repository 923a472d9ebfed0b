"""setup_s: seconds from process start to the measured window (loading,
warming and compiling the programs, preloading the data, warm traffic)."""


def read(run):
    return run.setup_s
