"""verify.pages_frac: share of the index's pages verified per query.

The program's own page counter (``SearchResult.stats["pages"]``, logical
page accesses of both verify rounds) summed over the window, over queries x
pages in the index. 1.0 means every query verified every page.
"""


def read(run):
    win = run.window
    if not win.queries:
        return None
    return win.pages / (win.queries * run.n_blocks)
