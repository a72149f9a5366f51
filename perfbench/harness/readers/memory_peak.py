"""Peak device memory in GB, on the fullest device: the sum of the named
``memory_stats()`` keys.  On this runtime ``peak_bytes_in_use`` counts live
buffers and ``peak_bytes_reserved`` the running program's temporaries; the two
coexist during a step, so their sum is the high-water mark (it matched the
compiler's arguments + temporaries to 1.3%: chip runs, PR 21)."""


def peak_bytes(memory, keys):
    sums = [sum(device.get(k, 0) for k in keys) for device in memory]
    return max(sums) if sums and max(sums) > 0 else None


def read(ctx, keys):
    peak = peak_bytes(ctx.measured["memory"], keys)
    return peak / 1e9 if peak else None
