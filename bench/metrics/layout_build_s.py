"""layout_build_s: seconds of `repro.engine.build_engine` in set-up (host
clock): the engine's host-side layout and its transfer to the device."""


def read(obs):
    return obs.layout_build_s
