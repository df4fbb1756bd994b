"""partition_s: the window's length over the partitions completed in
it. The partitions run back to back, so this is all the work over all
the time."""


def read(obs):
    return obs.window_s / obs.completed if obs.completed else None
