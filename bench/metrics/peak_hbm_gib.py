"""peak_hbm_gib: the highest ``peak_bytes_in_use`` over the cell's
devices, as the device runtime reports it after the window, in GiB."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30
