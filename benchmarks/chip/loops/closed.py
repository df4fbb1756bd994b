"""closed: a closed loop with one caller. Partitions run back to back
through ``Partitioner().run``, each on an input that no earlier
partition of the run had.

Warm-up: partitions of fresh inputs, until one of them compiles and
loads no program, at most ``MAX_WARMUPS``. Relabelled inputs reach
per-level shape buckets of their own, so one warm-up does not always
cover the next input. The last warm-up's time sizes the inputs that the
window gets; they are built before it opens.

Window: no partition starts once ``ctx.seconds`` have passed; the window
ends when the one in flight returns.
"""
import math
import sys
import time

MAX_WARMUPS = 5


def run(ctx) -> None:
    for _ in range(MAX_WARMUPS):
        before = ctx.compiles.count
        t0 = time.perf_counter()
        ctx.warm(ctx.new_input())
        last_s = time.perf_counter() - t0
        if ctx.compiles.count == before:
            break
    else:
        print(f"warm-up still compiled on partition {MAX_WARMUPS}",
              file=sys.stderr)
    first = len(ctx.inputs)
    print(f"warm-up: {first} partitions, the last {last_s:.3f} s",
          file=sys.stderr)
    for _ in range(math.ceil(ctx.seconds / last_s) + 1):
        ctx.new_input()
    with ctx.window() as clock:
        for i in range(first, len(ctx.inputs)):
            if clock() >= ctx.seconds:
                break
            ctx.call(i)
        else:
            if clock() < ctx.seconds:
                print(f"ran out of inputs after {ctx.attempted} "
                      f"partitions ({clock():.3f} s of {ctx.seconds} s)",
                      file=sys.stderr)
