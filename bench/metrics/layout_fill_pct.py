"""layout_fill_pct: % of the Pallas kernel layout's slots that hold a
nonzero, 100 · nnz / (T · P) over T tasks (padded to whole kernel calls)
of P slots each: the program's `layout.kernel_nonzeros` and
`layout.kernel_slots` counters.  Every slot is read by every mode's
kernel, filled or not."""

from bench.records import registry_metric


def read(obs):
    nonzeros = registry_metric(obs, "layout.kernel_nonzeros")
    slots = registry_metric(obs, "layout.kernel_slots")
    if not nonzeros or not slots or not slots["value"]:
        return None
    return 100.0 * nonzeros["value"] / slots["value"]
