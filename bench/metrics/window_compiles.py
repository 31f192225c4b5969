"""window_compiles: backend compilations inside the window (JAX's
`/jax/core/compile/backend_compile_duration` events).  Should read 0."""


def read(obs):
    return obs.window_compiles
