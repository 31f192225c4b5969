"""fit_host_ms: ms per iteration in `cp_als.fit`, the fit after each
iteration, while the device waits: ‖X‖ on the host (`cp_als.fit_norm`),
the dispatch of the fit's grams and products, and the readback of the
residual (`cp_als.fit_readback`).  The mean duration of the `cp_als.fit`
spans of the traced decomposition (host clock)."""

from bench.records import decomposition_spans, seconds


def read(obs):
    fits = seconds(decomposition_spans(obs), "cp_als.fit")
    return 1000.0 * sum(fits) / len(fits) if fits else None
