"""setup_s: seconds from process start to the window's start: JAX start,
graph generation, input build, warm-up and compile-cache loads."""


def read(obs):
    return obs.setup_s
