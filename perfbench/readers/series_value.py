"""One number the traffic kind recorded (a count, a depth), as it is."""


def read(ctx, series):
    return ctx.series.get(series)
