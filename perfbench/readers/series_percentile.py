"""A percentile of one of the traffic kind's series (host clock). With
``beyond``, only when that many samples lie beyond it (a tail needs ten)."""
from perfbench import stats


def read(ctx, series, q, beyond=0):
    values = ctx.series.get(series)
    if not values or not stats.supported(len(values), q, beyond):
        return None
    return stats.percentile(values, q)
