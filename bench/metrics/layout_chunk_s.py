"""layout_chunk_s: seconds of host chunking (`chunk_tensor`) in set-up, the
part of `layout_build_s` that packs the nonzeros into the chunk plan's
tasks: the program's `layout.chunk_seconds` histogram (host clock)."""

from bench.records import registry_metric


def read(obs):
    chunk = registry_metric(obs, "layout.chunk_seconds")
    return chunk["sum"] if chunk and chunk["count"] else None
