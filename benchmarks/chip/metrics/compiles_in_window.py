"""compiles_in_window: programs compiled, or loaded from the persistent
compile cache, while the window ran (jax.monitoring). Should be 0."""


def read(obs):
    return obs.compiles_in_window
