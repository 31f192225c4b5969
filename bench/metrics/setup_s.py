"""setup_s: seconds from process start to the start of the window (host clock).

Generation, the layout build, the transfer, compilation (from the
persistent cache after a cell's first run) and the warm-up decomposition."""


def read(obs):
    return obs.setup_s
