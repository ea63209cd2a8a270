"""attn_skipped_page_share (kernels): the share of the decode steps'
block-table slots that the paged-attention kernel does not fetch, in
percent: 100 x (1 - live / table) summed over the ``dispatch`` spans of
the window's engine steps, where ``live_pages`` counts each row's pages up
to its length and ``table_pages`` the rows times the table's width (the
batch's longest row).  How often the kernel's live-page bound engages
(program spans); a program whose spans carry no such counts reads
nothing."""

from bench import spans


def read(run):
    live = table = 0
    for s in spans.window_steps(run):
        for x in s.walk():
            if x.name == "dispatch" and "table_pages" in x.args:
                live += x.args["live_pages"]
                table += x.args["table_pages"]
    if not table:
        return None
    return 100.0 * (1.0 - live / table)
