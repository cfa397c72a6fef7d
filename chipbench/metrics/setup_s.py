"""Set-up time: from the process's start to the window's opening (building
the configuration, bootstrap, warm-up and every compile), on the host
clock."""


def read(run):
    return run.setup_s
