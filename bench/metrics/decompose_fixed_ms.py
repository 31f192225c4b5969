"""decompose_fixed_ms: ms per decomposition outside its iterations: the
initial factors drawn on the host and sent to the device (`cp_als.init`),
then the final factors and weights read back (`cp_als.readback`).  The
spans of the traced decomposition (host clock)."""

from bench.records import decomposition_spans, seconds


def read(obs):
    spans = decomposition_spans(obs)
    init, readback = seconds(spans, "cp_als.init"), seconds(spans, "cp_als.readback")
    if not init or not readback:
        return None
    return 1000.0 * (sum(init) + sum(readback))
