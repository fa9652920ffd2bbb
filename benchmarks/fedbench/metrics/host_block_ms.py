"""Host milliseconds per fused block in the window, from the server's own
``BlockTiming`` ledger: mean of ``dispatch_s + process_s`` (``sync_s``
is waiting on the device and is left out).  Nothing to read on a cell
without fused blocks."""


def read(ctx):
    blocks = ctx["window_blocks"]
    if not blocks:
        return None
    return 1e3 * sum(b.dispatch_s + b.process_s for b in blocks) / len(blocks)
