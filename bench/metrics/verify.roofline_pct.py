"""verify.roofline_pct: the verify kernel's share of the HBM roofline (%).

Least bytes per batch over the peak bandwidth, over the kernel's device
time per batch (``verify.kernel_ms``). The least bytes are the pages every
implementation must read once per batch: the mean logical pages a query
verified (the API reports the batch's total; the mean is no more than the
union of the pages the batch needs), at most the index's page count, times
page rows x d x item size. The bound is memory: a page's dot products with
B queries take 2 B flops per 4 bytes, far under the chip's ridge point.
"""
import trace_reduce


def read(run):
    ns = trace_reduce.kernel_ns(run)
    win = run.window
    if ns is None or not win.batches:
        return None
    pages = min(win.pages / win.queries, run.n_blocks)
    least_bytes = pages * run.page_rows * run.d * run.itemsize * win.batches
    least_s = least_bytes / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
