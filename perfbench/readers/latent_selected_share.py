"""Of the positions the live lanes hold, the share a decode step's full
layers attend over where a latent kind's indexer chooses among them: the
mean over the window of each lane's ``min(context, index_topk)`` (the
program's ``serve.stats`` ``live_chosen_positions``: the builder's
``mean_live_chosen_positions``) over the mean of the rows written
(``live_positions``: ``mean_live_positions``), in percent. None where the
system counts neither (a program from before the counter existed)."""


def read(ctx):
    seen = [getattr(ctx.system, name, lambda: None)() for name in (
        "mean_live_chosen_positions", "mean_live_positions")]
    if None in seen or not seen[1]:
        return None
    return 100.0 * seen[0] / seen[1]
