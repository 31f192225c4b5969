"""als_iter_s: seconds per CP-ALS iteration, fit included (host clock).

The window's whole length, to the end of its last decomposition, over
every iteration its decompositions completed."""


def read(obs):
    return obs.window_s / obs.iterations if obs.iterations else None
