"""Of the positions the live lanes hold, the share a decode step's
attention reads where an indexer chooses among them: the rows the decode
program says it read (the cache's ``chosen_rows`` as the window's last step
left them, all lanes: the builder's ``mean_selected_positions``) over the
mean over the window of the rows written (the program's ``serve.stats``
``live_positions``: ``mean_live_positions``), in percent. None where the
system counts neither."""
from perfbench.readers import selected_attention_roofline


def read(ctx):
    found = selected_attention_roofline.step_counts(ctx)
    if not found:
        return None
    _, live, chosen = found
    return 100.0 * chosen / live
