"""Peak device memory in use, of the limit the runtime reports, read after the window and before the reference."""


def read(ctx):
    peak, limit = ctx.memory.get("peak_bytes_in_use"), ctx.memory.get("bytes_limit")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
