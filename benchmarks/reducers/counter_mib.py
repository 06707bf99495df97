"""A byte count that the step itself reports in its metrics (`msg_bytes`: the
encoded gradient message one replica emits per step), in MiB. It repeats
exactly; `correct` holds it against the reference's count from shapes."""


def reduce(ctx, counter):
    value = ctx["counters"].get(counter)
    return value / 2**20 if value else None
