"""mttkrp_roofline: % of the roofline the engine's MTTKRP reaches.

Σ over modes of the least time (`roofline.least_time`: the shape's bytes
over HBM bandwidth or its FLOPs over peak, whichever is larger; memory bounds
every cell) over Σ over modes of the measured time of `engine(factors, mode)`
on the warmed engine: the device trace's busy time (the union of the op
intervals) inside each mode's annotation, over the calls made there.  Any
backend's ops count, named kernels and unnamed XLA fusions alike."""

from bench.roofline import least_time


def read(obs):
    if not obs.mode_s or not obs.peaks:
        return None
    least, _bound = least_time(obs.shape, obs.nnz, obs.rank, obs.peaks)
    return 100.0 * least * len(obs.mode_s) / sum(obs.mode_s)
