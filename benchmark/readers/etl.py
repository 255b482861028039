"""Mean milliseconds a step waited for its batch
(`TimedDataSetIterator.last_etl_ms`, as `fit` hands it to listeners)."""


def read(ctx):
    v = ctx.get("etl_ms")
    return sum(v) / len(v) if v else None
