"""als_driver_ms: the ALS driver's self time, in ms per iteration.

The window's time per iteration on the host clock, fit included, less the
MTTKRP time of one iteration: Σ over modes of the device time of
`engine(factors, mode)` on the warmed engine (see `mttkrp_roofline`).  What
is left is the grams, the pseudo-inverse, normalisation, the fit, the
driver's host work and the dispatch of it all."""


def read(obs):
    if not obs.mode_s or not obs.iterations:
        return None
    return 1000.0 * (obs.window_s / obs.iterations - sum(obs.mode_s))
