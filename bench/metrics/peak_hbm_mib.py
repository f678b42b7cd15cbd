"""Peak device memory in use (``peak_bytes_in_use``, fullest chip), read
right after the window, before the reference runs, in MiB."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 20 if ctx.peak_bytes else None
