"""Set-up time: from the process's start to the end of warm-up (loading,
making inputs and weights on the device, compiling or loading every
program the window uses)."""


def read(run):
    return run.setup_s
