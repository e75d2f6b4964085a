"""Mean per cell of the records' ``search_time_s``, in ms: the host search
and full evaluation of one campaign cell, as the program times it."""


def read(ctx):
    times = ctx.get("search_s")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
