"""Device time of collective operations per step (collective-permute,
all-reduce, all-to-all and the like), ms, on the slowest chip. An async
collective counts as its -start and -done ops on the device's op line, the
-done holding the wait for the transfer: the exposed time. Its span in
flight, which overlaps other work, is on a line of its own and not read."""
import devtrace as DT


def read(ctx):
    return DT.per_step_ms(ctx, DT.is_collective, over="max")
