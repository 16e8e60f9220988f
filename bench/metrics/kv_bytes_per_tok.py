"""Layout codecs: HBM bytes one cached token takes in the compressed
store over all served layers, from the arena's array shapes (payload and
quantisation scales per page slot)."""


def read(ctx):
    return ctx.kv["store_bytes_per_token"]
