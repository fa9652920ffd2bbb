"""Peak device memory after the untraced window, in GB (1e9 bytes), from
``memory_stats()["peak_bytes_in_use"]`` of the chip."""


def read(ctx):
    if not ctx["peak_bytes"]:
        return None
    return ctx["peak_bytes"] / 1e9
